"""DeepSeek-V3 (``model_type`` ``deepseek_v3``; arXiv:2412.19437) in plain
float32 ``jax.numpy``: a pre-norm decoder block with latent attention (MLA) in
its *plain*, unabsorbed form, YaRN rotary positions on a slice of the head, a
dense gated MLP in the leading layers and, after them, routed experts under
the sigmoid group-limited rule beside a shared expert. No cache, no batching,
no kernels; every product at ``highest``. One sequence at a time: ``tokens``
(T,), causal.

Weights are (in, out). A layer is a dict: ``ln1``, ``wq_a`` (H, q_rank),
``q_norm``, ``wq_b`` (q_rank, heads x (nope + rope)), ``wkv_a`` (H, kv_rank +
rope), ``kv_norm``, ``wkv_b`` (kv_rank, heads x (nope + v)), ``wo``, ``ln2``,
``mlp_gate`` / ``mlp_up`` / ``mlp_down`` (the dense layer's MLP, else the
shared expert) and, of a routed layer, ``router`` (H, E), ``router_bias`` (E,)
and the stacked ``w_gate`` / ``w_up`` (E_held, H, F), ``w_down`` (E_held, F,
H). ``dims`` is the configuration file's own keys; the router's width E is
``dims["published"]["n_routed_experts"]`` where the file holds a share.

Departures from the source, and what it does not give (``assumed`` in
``configs/deepseek_v3.json``):

- The rotated dimensions pair as split halves (dimension i with i + rope / 2),
  the layout the published modelling code reaches after permuting the
  checkpoint's interleaved columns of ``wq_b`` and ``wkv_a``: with seeded
  weights either is the same function.
- Experts outside the kept groups are left out of the choice (their ``c`` is
  -inf); the published code sets their ``c`` to 0, which is the same choice
  wherever a kept expert's ``c`` is positive.
- Experts are a masked dense product, one expert at a time: every held expert
  computes every row and rows not routed to it weigh zero. ``held`` = (first,
  count) names the share of the routed experts this chip holds; what the
  absent ones would add is left out, the shared expert is whole.
- The multi-token-prediction module is not part of the forward: the main
  model's logits do not pass through it.
- Float32 throughout; the published checkpoint is block-quantised float8.
"""
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


# ---------------------------------------------------------------------------
# rotary positions, YaRN
# ---------------------------------------------------------------------------
def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inverse_frequencies(dims):
    """The rope / 2 rotation rates: ``theta ** (-2i / rope)``, each blended
    with itself / ``factor`` by YaRN's linear ramp between the pair whose
    wavelength makes ``beta_fast`` turns in the original context (and every
    faster one: kept) and the pair that makes ``beta_slow`` (and every slower
    one: divided)."""
    rope, theta = dims["qk_rope_head_dim"], float(dims["rope_theta"])
    inv = [theta ** (-2.0 * i / rope) for i in range(rope // 2)]
    sc = dims.get("rope_scaling")
    if not sc:
        return jnp.asarray(inv, jnp.float32)
    span = sc["original_max_position_embeddings"]

    def pair_of(turns):
        return rope * math.log(span / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(sc["beta_fast"])), 0)
    high = min(math.ceil(pair_of(sc["beta_slow"])), rope - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(inv):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f * (1.0 - ramp) + f / sc["factor"] * ramp)
    return jnp.asarray(out, jnp.float32)


def rotary_scale(dims):
    """What cos and sin are multiplied by (1 for the published settings)."""
    sc = dims.get("rope_scaling")
    if not sc:
        return 1.0
    return yarn_mscale(sc["factor"], sc["mscale"]) \
        / yarn_mscale(sc["factor"], sc["mscale_all_dim"])


def softmax_scale(dims):
    """``(nope + rope) ** -1/2``, times YaRN's ``mscale ** 2``."""
    scale = (dims["qk_nope_head_dim"] + dims["qk_rope_head_dim"]) ** -0.5
    sc = dims.get("rope_scaling")
    if sc and sc.get("mscale_all_dim"):
        scale *= yarn_mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return scale


def rotate(x, positions, dims):
    """x (T, heads, rope): pairs (i, i + rope / 2) turned by position."""
    ang = positions.astype(jnp.float32)[:, None] * inverse_frequencies(dims)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return (x * cos + turned * sin) * rotary_scale(dims)


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------
def attention(x, p, positions, dims, head_block=None):
    """Plain latent attention over (T, H) rows, causal. ``head_block``:
    heads whose (T, T) scores are held at once (all by default; the on-chip
    check at 5,120 rows takes a few)."""
    T = x.shape[0]
    N, R = dims["num_attention_heads"], dims["kv_lora_rank"]
    dn, dv = dims["qk_nope_head_dim"], dims["v_head_dim"]
    eps = dims["rms_norm_eps"]
    c_q = rms_norm(_mm(x, p["wq_a"]), p["q_norm"], eps)
    q = _mm(c_q, p["wq_b"]).reshape(T, N, -1)
    kv = _mm(x, p["wkv_a"])
    c_kv = rms_norm(kv[:, :R], p["kv_norm"], eps)
    k_rope = rotate(kv[:, None, R:], positions, dims)      # one for all heads
    q = jnp.concatenate([q[..., :dn], rotate(q[..., dn:], positions, dims)],
                        -1)
    kv_h = _mm(c_kv, p["wkv_b"]).reshape(T, N, dn + dv)
    k = jnp.concatenate([kv_h[..., :dn],
                         jnp.broadcast_to(k_rope, (T, N, k_rope.shape[-1]))],
                        -1)
    v = kv_h[..., dn:]
    causal = positions[None, :] <= positions[:, None]
    scale = softmax_scale(dims)

    def some_heads(qkv):
        qh, kh, vh = qkv                                   # (T, heads, d)
        s = jnp.einsum("qhd,khd->hqk", qh, kh, precision=HIGHEST) * scale
        s = jnp.where(causal[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vh,
                          precision=HIGHEST)

    G = N // (head_block or N)
    by_group = lambda a: a.reshape(T, G, N // G, -1).transpose(1, 0, 2, 3)
    o = jax.lax.map(some_heads, (by_group(q), by_group(k), by_group(v)))
    return _mm(o.transpose(1, 0, 2, 3).reshape(T, N * dv), p["wo"])


def gated(h, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(h, w_gate)) * _mm(h, w_up), w_down)


def route(h, p, dims):
    """(T, E) weights over all E routed experts: ``s = sigmoid(h W_r)``; the
    choice is made on ``c = s + b``: a group's score is the sum of its two
    largest ``c``, the ``topk_group`` best of ``n_group`` groups are kept, and
    of their experts the ``num_experts_per_tok`` largest ``c``; a chosen
    expert weighs its ``s`` over the chosen's sum (``norm_topk_prob``), times
    ``routed_scaling_factor``; zero elsewhere."""
    s = jax.nn.sigmoid(_mm(h, p["router"]))
    c = s + p["router_bias"]
    T, E = s.shape
    groups = c.reshape(T, dims["n_group"], -1)
    score = jnp.sort(groups, -1)[..., -2:].sum(-1)
    kept = jnp.argsort(-score, -1)[:, :dims["topk_group"]]
    keep = jnp.zeros(score.shape, bool).at[jnp.arange(T)[:, None], kept] \
        .set(True)
    c = jnp.where(keep[:, :, None], groups, -jnp.inf).reshape(T, E)
    chosen = jnp.argsort(-c, -1)[:, :dims["num_experts_per_tok"]]
    picked = jnp.zeros((T, E), bool).at[jnp.arange(T)[:, None], chosen] \
        .set(True)
    w = jnp.where(picked, s, 0.0)
    if dims["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return w * dims["routed_scaling_factor"]


def routed(h, p, dims, held=None):
    """Sum over the held experts of weight x expert(h), an expert at a time;
    ``p``'s stacked arrays hold the held experts only."""
    first, count = held or (0, p["router"].shape[1])
    weights = route(h, p, dims)[:, first:first + count]

    def one(acc, ew):
        wg, wu, wd, w = ew
        return acc + w[:, None] * gated(h, wg, wu, wd), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (p["w_gate"], p["w_up"], p["w_down"], weights.T))
    return acc


def ffn(h, p, dims, held=None):
    """The dense layer's MLP; of a routed layer (it has a router) the held
    experts' part plus the shared expert, whole."""
    y = gated(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"])
    if "router" in p:
        y = y + routed(h, p, dims, held)
    return y


def layer(x, p, positions, dims, held=None, head_block=None):
    eps = dims["rms_norm_eps"]
    h = x + attention(rms_norm(x, p["ln1"], eps), p, positions, dims,
                      head_block)
    return h + ffn(rms_norm(h, p["ln2"], eps), p, dims, held)


def head_logits(x, params, dims):
    return _mm(rms_norm(x, params["final_norm"], dims["rms_norm_eps"]),
               params["head"])


def forward(params, tokens, dims, held=None):
    """(T,) tokens -> (T, V) logits."""
    positions = jnp.arange(len(tokens))
    x = params["embed"][tokens]
    for p in params["layers"]:
        x = layer(x, p, positions, dims, held)
    return head_logits(x, params, dims)
