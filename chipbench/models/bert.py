"""BERT pretraining (MLM + NSP) as bench.py's bench_bert builds it: the model
zoo's BERTForPretraining, the MLM head at the masked positions only."""
import types

import numpy as onp

from ..reference import transformer as reference


def masked_positions(seq_len, mlm_probability):
    """Masked positions per sample: the paper's 15% of the sequence, a fixed
    count so that every sample has one shape (19 of 128)."""
    return max(1, int(seq_len * mlm_probability))


def flops_per_sample(config, cell):
    """Forward + backward FLOPs one sample requires (backward = 2 x forward,
    nothing recomputed, a multiply-add = 2): every layer at every token, the
    MLM head at the masked positions only, pooler and NSP once. The one-hot
    gather the program uses to pick those positions is its own choice and is
    not counted."""
    u, f = config["hidden_size"], config["intermediate_size"]
    s = cell["seq_len"]
    per_token_layer = (2 * u * 3 * u          # q, k, v
                       + 2 * s * u * 2        # scores and weighted values
                       + 2 * u * u            # output projection
                       + 2 * u * f * 2)       # feed-forward, in and out
    body = config["num_hidden_layers"] * per_token_layer * s
    p = masked_positions(s, cell["mlm_probability"])
    head = p * (2 * u * u + 2 * u * config["vocab_size"])
    pooled = 2 * u * u + 2 * u * 2
    return 3.0 * (body + head + pooled)


def build_train(config, cell, seed, context):
    import mxnet_tpu as mx
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo import bert

    mx.random.seed(seed)
    onp.random.seed(seed)
    vocab, seq = config["vocab_size"], cell["seq_len"]
    with context:          # initialised where it will train, not on the host
        backbone = bert.BERTModel(
            num_layers=config["num_hidden_layers"],
            units=config["hidden_size"],
            hidden_size=config["intermediate_size"],
            num_heads=config["num_attention_heads"], vocab_size=vocab,
            max_length=config["max_position_embeddings"],
            type_vocab_size=config["type_vocab_size"],
            dropout=config["hidden_dropout_prob"])
        model = bert.BERTForPretraining(backbone, vocab_size=vocab)
        model.initialize(mx.init.Normal(config["initializer_range"]))

    class _PretrainStep(HybridBlock):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def forward(self, tokens, token_types, positions):
            return self.inner(tokens, token_types, None, positions)

    n_pred = masked_positions(seq, cell["mlm_probability"])

    def make_batches(key, k, samples):
        """K micro-batches of random ids, in the argument order of step_n:
        tokens, (mlm labels, nsp labels), token types, masked positions."""
        import jax
        import jax.numpy as jnp
        kt, kp, km, kn = jax.random.split(key, 4)
        toks = jax.random.randint(kt, (k, samples, seq), 0, vocab, jnp.int32)
        order = jnp.argsort(jax.random.uniform(kp, (k, samples, seq)), -1)
        positions = jnp.sort(order[..., :n_pred], -1).astype(jnp.int32)
        mlm = jax.random.randint(km, (k, samples, n_pred), 0, vocab, jnp.int32)
        nsp = jax.random.randint(kn, (k, samples), 0, 2, jnp.int32)
        return toks, (mlm, nsp), jnp.zeros_like(toks), positions

    def reference_loss(batches):
        """The plain reference's loss on the first micro-batch with the
        system's initial weights (call before the first step)."""
        import jax
        toks, (mlm, nsp), types_, positions = batches
        fn = jax.jit(lambda p, *a: reference.bert_pretrain_loss(
            p, *a, heads=config["num_attention_heads"]))
        return float(fn(reference_params(model), toks[0], types_[0],
                        positions[0], mlm[0], nsp[0]))

    return types.SimpleNamespace(
        block=_PretrainStep(model), loss=bert.BERTPretrainingLoss(),
        optimizer=mx.optimizer.Adam(learning_rate=cell["learning_rate"]),
        extra_specs=(P("dp"), P("dp")), compute_dtype=cell["compute_dtype"],
        make_batches=make_batches, reference_loss=reference_loss,
        rates={"tokens_per_s": seq})


def _wb(dense):
    return (_f32(dense.weight), _f32(dense.bias))


def _ln(norm):
    return (_f32(norm.gamma), _f32(norm.beta))


def _f32(param):
    import jax.numpy as jnp
    return jnp.asarray(param.data().data, jnp.float32)


def encoder_params(embedder, encoder):
    """The reference's parameter tree for the embeddings and layers that
    BERTModel and TransformerLM share."""
    tree = {"word": _f32(embedder.word_embed.weight),
            "position": _f32(embedder.position_embed.weight),
            "embed_ln": _ln(embedder.embed_ln), "layers": []}
    for lyr in encoder._layers:
        tree["layers"].append({
            "qkv": _wb(lyr.attention.qkv), "proj": _wb(lyr.attention.proj),
            "ln1": _ln(lyr.ln1), "ffn1": _wb(lyr.ffn.ffn1),
            "ffn2": _wb(lyr.ffn.ffn2), "ln2": _ln(lyr.ln2)})
    return tree


def reference_params(model):
    tree = encoder_params(model.backbone, model.backbone.encoder)
    tree.update(type=_f32(model.backbone.token_type_embed.weight),
                pooler=_wb(model.backbone.pooler),
                mlm_transform=_wb(model.mlm_transform),
                mlm_ln=_ln(model.mlm_ln), nsp=_wb(model.nsp))
    return tree
