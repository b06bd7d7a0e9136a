"""The repo's decoder-only TransformerLM (the BERT encoder stack with a causal
mask, learned positions, a tied head) at a public model's sizes."""
import functools

import numpy as onp

from ..reference import transformer as reference
from .bert import encoder_params


def build_lm(config, seed):
    """The model, initialised N(0, initializer_range) from ``seed`` on the
    current context, its position embeddings ``position_init_scale`` times
    as wide."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.bert import TransformerLM

    mx.random.seed(seed)
    onp.random.seed(seed)
    lm = TransformerLM(
        num_layers=config["n_layer"], units=config["n_embd"],
        hidden_size=4 * config["n_embd"], num_heads=config["n_head"],
        vocab_size=config["vocab_size"], max_length=config["n_positions"])
    # the configuration's ``assumed.position_init_scale`` says why
    lm.position_embed.initialize(mx.init.Normal(
        config["position_init_scale"] * config["initializer_range"]))
    lm.initialize(mx.init.Normal(config["initializer_range"]))
    return lm


@functools.lru_cache(maxsize=None)
def _jitted_logits(heads):
    import jax
    return jax.jit(lambda p, t: reference.lm_logits(p, t, heads))


def reference_logits(lm, config, tokens):
    """The plain reference's logits (B, S, V) for (B, S) token ids under the
    system's weights."""
    import jax.numpy as jnp
    return _jitted_logits(config["n_head"])(
        encoder_params(lm, lm.encoder), jnp.asarray(tokens, jnp.int32))


def control_logits(lm, config, tokens):
    """The control: the same reference with weights and activations in
    bfloat16, the nearest precision below the float32 the configuration
    states and the step that would tempt a later PR. Not run by the
    benchmark's own runs (``chipbench.control`` and its test do)."""
    import jax
    import jax.numpy as jnp
    low = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.bfloat16),
                                 encoder_params(lm, lm.encoder))
    return _jitted_logits(config["n_head"])(
        low, jnp.asarray(tokens, jnp.int32))

