"""Post-LN transformer in plain float32 ``jax.numpy``: one layer function,
shared by BERT pretraining (bidirectional, MLM + NSP loss) and the causal
language model (GPT-1 sizes). Dense weights are (out, in), as published
checkpoints of both models store them after transposition and as the program
holds them; q, k and v are thirds of one fused projection, heads contiguous.

Departures from the papers, which follow the program under test and are noted
in the configuration files: no bias on the tied output decoder; the causal LM
normalises its embeddings once (GPT-1 does not).
"""
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _dense(x, wb):
    w, b = wb
    return jnp.matmul(x, w.T, precision=HIGHEST) + b


def layer_norm(x, gb, eps=1e-5):
    g, b = gb
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                   * (x + 0.044715 * x ** 3)))


def gelu_erf(x):
    return 0.5 * x * (1 + jax.lax.erf(x / math.sqrt(2.0)))


def layer(x, p, heads, causal):
    """One post-LN layer over x (B, S, U)."""
    B, S, U = x.shape
    q, k, v = jnp.split(_dense(x, p["qkv"]), 3, axis=-1)
    split = lambda t: t.reshape(B, S, heads, U // heads).transpose(0, 2, 1, 3)
    scores = jnp.einsum("bhqd,bhkd->bhqk", split(q), split(k),
                        precision=HIGHEST) / math.sqrt(U // heads)
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((S, S), bool)), scores, -jnp.inf)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), split(v),
                     precision=HIGHEST)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, U)
    x = layer_norm(x + _dense(ctx, p["proj"]), p["ln1"])
    ffn = _dense(gelu_tanh(_dense(x, p["ffn1"])), p["ffn2"])
    return layer_norm(x + ffn, p["ln2"])


def encode(params, tokens, token_types, heads, causal):
    """Embeddings (word + position [+ type], LayerNorm) and every layer."""
    S = tokens.shape[1]
    h = params["word"][tokens] + params["position"][:S]
    if token_types is not None:
        h = h + params["type"][token_types]
    h = layer_norm(h, params["embed_ln"])
    for p in params["layers"]:
        h = layer(h, p, heads, causal)
    return h


def _nll(logits, labels):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]


def bert_pretrain_loss(params, tokens, token_types, positions, mlm_labels,
                       nsp_labels, heads):
    """Mean masked-LM loss over the masked ``positions`` (B, P) plus mean
    next-sentence loss; no dropout."""
    seq = encode(params, tokens, token_types, heads, causal=False)
    pooled = jnp.tanh(_dense(seq[:, 0], params["pooler"]))
    at = jnp.take_along_axis(seq, positions[..., None], axis=1)   # (B, P, U)
    h = layer_norm(gelu_erf(_dense(at, params["mlm_transform"])),
                   params["mlm_ln"])
    mlm_logits = jnp.matmul(h, params["word"].T, precision=HIGHEST)
    nsp_logits = _dense(pooled, params["nsp"])
    return _nll(mlm_logits, mlm_labels).mean() + \
        _nll(nsp_logits, nsp_labels).mean()


def lm_logits(params, tokens, heads):
    """Causal LM: (B, S) tokens -> (B, S, V) logits through the tied head."""
    h = encode(params, tokens, None, heads, causal=True)
    return jnp.matmul(h, params["word"].T, precision=HIGHEST)
