"""Mellum 2 (JetBrains Mellum2-12B-A2.5B-Instruct, ``model_type`` ``mellum``)
in plain float32 ``jax.numpy``: a pre-norm decoder block with grouped KV
heads, per-head q/k RMSNorm, rotary positions and routed SwiGLU experts, in
which three layers of every four see only the last ``sliding_window``
positions and the fourth sees everything, each kind under its own rotary
table (plain for the window layers, YaRN's for the full ones). No cache, no
batching, no kernels; every product at ``highest``. One sequence at a time:
``tokens`` (T,), causal.

Weights are (in, out). A layer is a dict: ``ln1``, ``wq`` (H, heads x D),
``wk``/``wv`` (H, kv_heads x D), ``q_norm``/``k_norm`` (D,), ``wo``, ``ln2``,
``router`` (H, E) and the stacked ``w_gate``/``w_up`` (E, H, F), ``w_down``
(E, F, H). ``dims`` is the configuration file's own keys; layer l's kind is
``dims["layer_types"][l]`` and its rotary table ``dims["rope_parameters"]``'s
entry of that name.

Departures from the published description, and what it does not give
(``assumed`` in ``configs/mellum2_12b.json``):

- q and k are RMS-normalised per head, with a learned weight over the head's
  128 dimensions, before the rotation: the config has no key for it; its keys
  are the Qwen3-MoE lineage's, whose blocks have it.
- The mask's convention: position i sees position j iff ``j <= i`` and, in a
  sliding layer, ``i - j < sliding_window``: the window's 1,024 positions
  with i itself among them (the published masking code's convention as
  recalled; the config states only the number).
- YaRN's frequencies are written out here and not imported from the program:
  each ``theta ** (-2i / D)`` blended with itself / ``factor`` by the linear
  ramp between the pair that makes ``beta_fast`` turns in the original context
  and the pair that makes ``beta_slow``; cos and sin times the config's
  ``attention_factor``. The softmax scale stays ``D ** -1/2`` in both kinds.
- Experts are a masked dense product, one expert at a time: every expert
  computes every row and rows not routed to it weigh zero.
- The prediction module the model's card names ("MTP head") is not part of the
  forward: the config has no key for it and the main model's logits do not
  pass through it.
- Float32 throughout; the served dtype is bfloat16.
"""
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
SLIDING = "sliding_attention"


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


# ---------------------------------------------------------------------------
# rotary positions: plain, and YaRN's
# ---------------------------------------------------------------------------
def inverse_frequencies(rope, dim):
    """(the ``dim // 2`` rotation rates as a list of floats, what cos and sin
    are multiplied by) for one entry of ``rope_parameters``."""
    theta = float(rope["rope_theta"])
    inv = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    if rope["rope_type"] == "default":
        return inv, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    span, factor = rope["original_max_position_embeddings"], rope["factor"]

    def pair_of(turns):     # the pair whose wavelength makes ``turns`` turns
        return dim * math.log(span / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(inv):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f * (1.0 - ramp) + f / factor * ramp)
    return out, float(rope["attention_factor"])


def rotate(x, positions, rope):
    """x (T, heads, D): pairs (i, i + D / 2) turned by position under the
    table of ``rope``."""
    D = x.shape[-1]
    inv, scale = inverse_frequencies(rope, D)
    ang = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :] * scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :] * scale
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + half * sin


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------
def seen(q_pos, k_pos, kind, dims):
    """(Tq, Tk) bool: the mask built from positions."""
    ok = k_pos[None, :] <= q_pos[:, None]
    if kind == SLIDING:
        ok &= q_pos[:, None] - k_pos[None, :] < dims["sliding_window"]
    return ok


def attention(x, p, positions, kind, dims, head_block=None, row_block=None):
    """Dense masked attention over (T, H) rows. ``head_block`` /
    ``row_block``: heads and query rows whose scores are held at once (all by
    default; the on-chip check at 16,384 rows takes one head and a block of
    rows at a time, so that 16,384 squared scores never stand whole)."""
    T = x.shape[0]
    Hq, Hkv, D = (dims["num_attention_heads"], dims["num_key_value_heads"],
                  dims["head_dim"])
    eps, rope = dims["rms_norm_eps"], dims["rope_parameters"][kind]
    q = _mm(x, p["wq"]).reshape(T, Hq, D)
    k = _mm(x, p["wk"]).reshape(T, Hkv, D)
    v = _mm(x, p["wv"]).reshape(T, Hkv, D)
    q = rotate(rms_norm(q, p["q_norm"], eps), positions, rope)
    k = rotate(rms_norm(k, p["k_norm"], eps), positions, rope)
    # query head h attends with KV head h // (Hq / Hkv)
    k = jnp.repeat(k, Hq // Hkv, axis=1)
    v = jnp.repeat(v, Hq // Hkv, axis=1)
    rows = row_block or T
    G = Hq // (head_block or Hq)
    if T % rows or Hq % G:
        raise ValueError(f"{T} rows in blocks of {rows}, {Hq} heads in {G}")

    def some_heads(qkv):
        qh, kh, vh = qkv                                # (T, heads, D)

        def some_rows(args):
            qr, pos = args                              # (rows, heads, D)
            s = jnp.einsum("qhd,khd->hqk", qr, kh, precision=HIGHEST) \
                / math.sqrt(D)
            s = jnp.where(seen(pos, positions, kind, dims)[None], s,
                          -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vh,
                              precision=HIGHEST)

        o = jax.lax.map(some_rows, (qh.reshape(T // rows, rows, -1, D),
                                    positions.reshape(T // rows, rows)))
        return o.reshape(T, -1, D)

    by_group = lambda a: a.reshape(T, G, Hq // G, D).transpose(1, 0, 2, 3)
    o = jax.lax.map(some_heads, (by_group(q), by_group(k), by_group(v)))
    return _mm(o.transpose(1, 0, 2, 3).reshape(T, Hq * D), p["wo"])


def route(h, router, dims):
    """(T, E) weights: a softmax over all experts, the
    ``num_experts_per_tok`` largest kept and (``norm_topk_prob``) divided by
    their sum; zero elsewhere."""
    probs = jax.nn.softmax(_mm(h, router), -1)
    top_p, top_i = jax.lax.top_k(probs, dims["num_experts_per_tok"])
    if dims["norm_topk_prob"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    at = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(probs).at[at, top_i].set(top_p)


def gated(h, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(h, w_gate)) * _mm(h, w_up), w_down)


def experts(h, p, dims):
    """Sum over the experts of weight x W_down(silu(W_gate h) * W_up h), a
    loop over experts; nothing dropped."""
    weights = route(h, p["router"], dims)

    def one(acc, ew):
        wg, wu, wd, w = ew
        return acc + w[:, None] * gated(h, wg, wu, wd), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (p["w_gate"], p["w_up"], p["w_down"], weights.T))
    return acc


def layer(x, p, positions, kind, dims, head_block=None, row_block=None):
    eps = dims["rms_norm_eps"]
    h = x + attention(rms_norm(x, p["ln1"], eps), p, positions, kind, dims,
                      head_block, row_block)
    return h + experts(rms_norm(h, p["ln2"], eps), p, dims)


def head_logits(x, params, dims):
    return _mm(rms_norm(x, params["final_norm"], dims["rms_norm_eps"]),
               params["head"])


def forward(params, tokens, dims, every_layer_full=False):
    """(T,) tokens -> (T, V) logits. ``every_layer_full``: the stand-in that
    ignores the window (every layer masks as a full one; each keeps its own
    rotary table)."""
    positions = jnp.arange(len(tokens))
    x = params["embed"][tokens]
    for p, kind in zip(params["layers"], dims["layer_types"]):
        x = layer(x, p, positions, kind, dims) if not every_layer_full else \
            layer(x, p, positions, kind, {**dims, "sliding_window": 1 << 30})
    return head_logits(x, params, dims)
