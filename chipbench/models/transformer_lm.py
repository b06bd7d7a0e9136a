"""The repo's decoder-only TransformerLM (the BERT encoder stack with a causal
mask, learned positions, a tied head) at a public model's sizes."""
import numpy as onp

from ..reference import transformer as reference
from .bert import encoder_params


def build_lm(config, seed):
    """The model, initialised N(0, initializer_range) from ``seed`` on the
    current context."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.bert import TransformerLM

    mx.random.seed(seed)
    onp.random.seed(seed)
    lm = TransformerLM(
        num_layers=config["n_layer"], units=config["n_embd"],
        hidden_size=4 * config["n_embd"], num_heads=config["n_head"],
        vocab_size=config["vocab_size"], max_length=config["n_positions"])
    lm.initialize(mx.init.Normal(config["initializer_range"]))
    return lm


def reference_logits(lm, config, tokens):
    """The plain reference's logits (B, S, V) for (B, S) token ids under the
    system's weights."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda p, t: reference.lm_logits(p, t, config["n_head"]))
    return fn(encoder_params(lm, lm.encoder), jnp.asarray(tokens, jnp.int32))

