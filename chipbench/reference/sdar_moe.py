"""SDAR-MoE (JetLM SDAR-30B-A3B-Chat, ``model_type`` ``sdar_moe``) in plain
float32 ``jax.numpy``: a pre-norm decoder block with grouped KV heads, rotary
positions, per-head q/k RMSNorm and routed SwiGLU experts, the mask of block
diffusion, and its greedy static generation rule. No cache, no batching, no
kernels; every product at ``highest``. One sequence at a time: ``tokens`` (T,),
``positions`` (T,), ``mask`` (T, T) bool, so that a caller can lay a clean
sequence and its noisy copies side by side (:func:`unroll`).

Weights are (in, out); a layer's experts are three stacked arrays
``w_gate``/``w_up`` (E, H, F) and ``w_down`` (E, F, H). ``dims`` is the
configuration file's own keys.

Departures from the source, and what it does not give (``assumed`` in
``configs/sdar_30b_a3b.json``):

- q and k are RMS-normalised per head before the rotation, the Qwen3-MoE
  convention ``sdar_moe`` derives from; the config has no key for it.
- ``block_length``, ``mask_token_id`` and the schedule are not in the config:
  a block of ``block_length`` positions is denoised in ``denoising_steps``
  steps, each placing the ``block_length / denoising_steps`` still-masked
  positions of highest confidence (ties to the lower position).
- The mask token is never a candidate: its logit is taken out before the
  arg-max and the confidence's softmax.
- A prompt's ragged tail fills the first positions of the first block and
  counts as placed; positions past ``max_new_tokens`` in the last block stay
  masked and are never placed.
- Experts are a masked dense product: every held expert computes every row and
  rows not routed to it weigh zero. ``held`` = (first, count) names the share
  of the experts this chip holds; what the absent ones would add is left out.
"""
import math

import jax
import jax.numpy as jnp
import numpy as onp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotate(x, positions, theta):
    """Rotary positions over the whole head, rotate-half: x (T, heads, D)."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + half * sin


def block_mask(q_pos, k_pos, block_length):
    """Position i sees position j iff j's block is not after i's: both
    directions inside a block, causal between blocks."""
    return (k_pos[None, :] // block_length) <= (q_pos[:, None] // block_length)


def attention(x, p, positions, mask, dims):
    T = x.shape[0]
    Hq, Hkv, D = (dims["num_attention_heads"], dims["num_key_value_heads"],
                  dims["head_dim"])
    eps, theta = dims["rms_norm_eps"], float(dims["rope_theta"])
    q = _mm(x, p["wq"]).reshape(T, Hq, D)
    k = _mm(x, p["wk"]).reshape(T, Hkv, D)
    v = _mm(x, p["wv"]).reshape(T, Hkv, D)
    q = rotate(rms_norm(q, p["q_norm"], eps), positions, theta)
    k = rotate(rms_norm(k, p["k_norm"], eps), positions, theta)
    # query head h attends with KV head h // (Hq / Hkv)
    k = jnp.repeat(k, Hq // Hkv, axis=1)
    v = jnp.repeat(v, Hq // Hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(D)
    s = jnp.where(mask[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST)
    return _mm(o.reshape(T, Hq * D), p["wo"])


def route(h, router, dims):
    """(T, E) weights: the softmax over all experts, the
    ``num_experts_per_tok`` largest kept and (``norm_topk_prob``) divided by
    their sum; zero elsewhere."""
    probs = jax.nn.softmax(_mm(h, router), -1)
    top_p, top_i = jax.lax.top_k(probs, dims["num_experts_per_tok"])
    if dims["norm_topk_prob"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, top_i].set(top_p)


def experts(h, p, dims, held=None):
    """Sum over the held experts of weight x W_down(silu(W_gate h) * W_up h);
    ``p``'s stacked arrays hold the held experts only."""
    first, count = held or (0, dims["num_experts"])
    weights = route(h, p["router"], dims)[:, first:first + count]

    def one(acc, ew):
        wg, wu, wd, w = ew
        y = _mm(jax.nn.silu(_mm(h, wg)) * _mm(h, wu), wd)
        return acc + w[:, None] * y, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (p["w_gate"], p["w_up"], p["w_down"], weights.T))
    return acc


def layer(x, p, positions, mask, dims, held=None):
    eps = dims["rms_norm_eps"]
    h = x + attention(rms_norm(x, p["ln1"], eps), p, positions, mask, dims)
    return h + experts(rms_norm(h, p["ln2"], eps), p, dims, held)


def head_logits(x, params, dims):
    return _mm(rms_norm(x, params["final_norm"], dims["rms_norm_eps"]),
               params["head"])


def forward(params, tokens, positions, mask, dims, held=None):
    """(T,) tokens -> (T, V) logits."""
    x = params["embed"][tokens]
    for p in params["layers"]:
        x = layer(x, p, positions, mask, dims, held)
    return head_logits(x, params, dims)


# ---------------------------------------------------------------------------
# the generation rule
# ---------------------------------------------------------------------------
def candidates(logits, mask_id):
    """Per row of (R, V) logits: the arg-max token other than the mask token
    and its softmax probability, in float32."""
    logits = jnp.asarray(logits, jnp.float32).at[:, mask_id].set(-jnp.inf)
    top = logits.max(-1)
    conf = 1.0 / jnp.exp(logits - top[:, None]).sum(-1)
    return logits.argmax(-1), conf


def place(conf, masked, n):
    """The ``n`` masked positions of highest confidence (fewer if fewer are
    masked), ties to the lower position."""
    order = sorted((i for i, m in enumerate(masked) if m),
                   key=lambda i: (-float(conf[i]), i))
    return sorted(order[:n])


def generate(params, prompt, max_new_tokens, dims, denoising_steps,
             held=None, logits_of=None):
    """Greedy block diffusion with no cache: every step is a full forward of
    everything so far under the block mask (``logits_of(tokens)``, if given,
    stands for that forward: a caller's jitted one). Returns (tokens, steps,
    confidences): the ``max_new_tokens`` generated ids and, for each, the
    step of its block at which it was placed and the confidence it was
    placed with."""
    L, mask_id = dims["block_length"], dims["mask_token_id"]
    per_step = L // denoising_steps
    seq = list(prompt)
    n, end = len(prompt), len(prompt) + max_new_tokens
    placed_at = {}
    start = n // L * L
    while start < end:
        block = seq[start:] + [mask_id] * (start + L - len(seq))
        masked = [n <= start + i < end for i in range(L)]
        step = 0
        while any(masked):
            toks = onp.asarray(seq[:start] + block, onp.int32)
            if logits_of is None:
                pos = onp.arange(len(toks))
                logits = forward(params, toks, pos, block_mask(pos, pos, L),
                                 dims, held)
            else:
                logits = logits_of(toks)
            tok, conf = candidates(logits[start:], mask_id)
            for i in place(conf, masked, per_step):
                block[i], masked[i] = int(tok[i]), False
                placed_at[start + i] = (step, float(conf[i]))
            step += 1
        seq = seq[:start] + block
        start += L
    steps, confidences = zip(*(placed_at[i] for i in range(n, end)))
    return seq[n:end], list(steps), list(confidences)


# ---------------------------------------------------------------------------
# every (block, step) state of one finished request in one forward
# ---------------------------------------------------------------------------
def unroll(prompt, answer, placed_at, dims, denoising_steps, clean_rows,
           noisy_rows):
    """The clean sequence followed by ``denoising_steps`` noisy copies of its
    generated region: copy ``s`` holds every block as it stood before its step
    ``s``. A noisy row sees the clean rows of earlier blocks and its own
    copy's rows of its own block, so one forward reaches every state. Returns
    a dict of row vectors (tokens, positions, copy: -1 clean / s noisy, valid)
    padded to ``clean_rows + denoising_steps * noisy_rows`` rows, with
    ``states``: for each (block, step) with something masked, the noisy rows
    of the block, which of them were masked, which were placed then, the
    tokens placed and each row's index into the answer (negative: the
    prompt's tail)."""
    L, mask_id = dims["block_length"], dims["mask_token_id"]
    n, m = len(prompt), len(answer)
    start = n // L * L
    blocks = -(-(n + m - start) // L)
    region = blocks * L
    if start + region > clean_rows or region > noisy_rows:
        raise ValueError("the sequence does not fit the rows it was given")
    T = clean_rows + denoising_steps * noisy_rows
    tokens = onp.full(T, mask_id, onp.int32)
    positions = onp.zeros(T, onp.int32)
    copy = onp.full(T, -1, onp.int32)
    valid = onp.zeros(T, bool)
    seq = list(prompt) + list(answer)
    tokens[:len(seq)] = seq
    positions[:clean_rows] = onp.arange(clean_rows)
    valid[:start + region] = True
    # step at which each position of the region was placed: -1 the prompt's
    # tail, None past the budget (never placed)
    when = [-1] * (n - start) + list(placed_at) + \
        [None] * (start + region - n - m)
    states = []
    for s in range(denoising_steps):
        at = clean_rows + s * noisy_rows
        for r in range(region):
            shown = when[r] is not None and when[r] < s
            tokens[at + r] = seq[start + r] if shown else mask_id
        positions[at:at + region] = start + onp.arange(region)
        copy[at:at + noisy_rows] = s
        for b in range(blocks):
            rows = range(b * L, (b + 1) * L)
            masked = [when[r] is not None and when[r] >= s for r in rows]
            if any(masked):
                valid[at + b * L:at + (b + 1) * L] = True
                states.append({
                    "block": b, "step": s,
                    "rows": [at + r for r in rows], "masked": masked,
                    "placed": [when[r] == s for r in rows],
                    "tokens": [seq[start + r] if start + r < len(seq)
                               else mask_id for r in rows],
                    "answer": [start + r - n for r in rows]})
    return {"tokens": tokens, "positions": positions, "copy": copy,
            "valid": valid, "states": states}


def unrolled_mask(positions, copy, valid, block_length):
    """The (T, T) mask of :func:`unroll`'s rows; a padding row sees itself."""
    blk = positions // block_length
    clean_k = (copy == -1)[None, :]
    clean_q = (copy == -1)[:, None]
    sees_clean = clean_k & jnp.where(clean_q, blk[None, :] <= blk[:, None],
                                     blk[None, :] < blk[:, None])
    sees_own = ~clean_q & (copy[None, :] == copy[:, None]) & \
        (blk[None, :] == blk[:, None])
    ok = (sees_clean | sees_own) & valid[None, :] & valid[:, None]
    return ok | jnp.eye(len(positions), dtype=bool)
