"""Flash attention Pallas kernel.

The TPU-native replacement for the reference's fused interleaved attention
matmuls (src/operator/contrib/transformer.cc:650-828): instead of two fused
batched GEMMs materializing the (S x S) score matrix in HBM, the kernel tiles
Q into VMEM blocks and streams K/V blocks through VMEM with the online-softmax
running (max, sum, out) accumulation — HBM traffic O(S·D) instead of O(S²),
and every tile lands on the MXU at (block, head_dim) granularity.

Forward is the Pallas kernel; backward is a custom VJP that recomputes
attention blockwise with XLA einsums (the standard recompute-style flash
backward; Pallas backward kernel is a further optimization).

Layout: (B, H, S, D) with D the head dim. D should be a multiple of 128 lanes
or small enough to pad; S blocks of 128/256 keep the MXU shape-friendly.

A **window** (causal, forward only): row i sees column j iff ``j <= i`` and
``i - j < window``, the last ``window`` positions with itself among them. The
grid's k axis then runs over the blocks of the band alone, not over all of S:
a q block's first k block is the one that holds its first row's oldest
column, and blocks wholly behind the band are never visited, as blocks above
the diagonal are never computed; a 16,384-row layer under a window of 1,024
costs an eighth of the causal one. **Grouped KV heads**: ``k`` and ``v`` may
have fewer heads than ``q`` (a divisor); query head h reads KV head ``h //
(H / H_kv)`` through the k and v blocks' index map, and nothing is repeated
in HBM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..registry import register

# defaults from on-chip v5e sweeps (D=64, causal): 512/1024 runs ~30%
# faster than 128/128 at S=4096 (fewer grid steps, larger MXU ops) and
# ~10-25% faster than jax.experimental.pallas.ops.tpu.flash_attention at
# the same shapes; both clamp to S for short sequences. At very long
# context the optimum shifts up: S>=16384 runs ~30% faster fwd and ~12%
# faster bwd at 1024/1024 (r5 sweep of block sizes on the chip) —
# resolved adaptively in flash_attention() when the caller does not
# override the blocks.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
_LONG_S = 16384
_LONG_BLOCK_Q = 1024
_LONG_BLOCK_K = 1024
_NEG_INF = -1e30
_LANES = 128  # TPU lane width; lse is broadcast across it for layout legality


def _dot_precision(dtype):
    """Explicit contraction precision for every kernel dot (Mosaic ignores
    no kwarg — it inherits the GLOBAL jax_default_matmul_precision=highest
    set in mxnet_tpu/__init__.py, and REJECTS that f32-emulation request on
    bf16 MXU operands: "Bad lhs type" at compile time, real hardware only —
    interpret mode never sees it; tests/test_pallas_source_guards.py pins
    the kwarg's presence). bf16 operands: DEFAULT — a single MXU pass is
    already exact bf16. f32 operands: HIGHEST — keeps the package's
    fp32-exactness contract inside the kernel too."""
    return (jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16
            else jax.lax.Precision.HIGHEST)


def _masked_scores(q, k_blk, sm_scale, mask_causal, mask_tail, q_offset,
                   k_offset, block_q, block_k, seq_len, window=None):
    """q @ k^T * scale with the causal/padded-tail masks this block class
    needs. Dots stay in the input dtype (bf16 MXU-native) with fp32
    accumulation — casting operands to fp32 first would run the MXU at its
    8x-slower fp32 rate. Shared by the forward and both backward kernels so
    the masking logic exists exactly once."""
    s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=_dot_precision(q.dtype)) * sm_scale
    if mask_causal or mask_tail:
        cols = k_offset + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = cols < seq_len if mask_tail else None
        if mask_causal:
            rows = q_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            causal_ok = rows >= cols
            if window is not None:
                causal_ok &= rows - cols < window
            valid = causal_ok if valid is None else (valid & causal_ok)
        s = jnp.where(valid, s, _NEG_INF)
    return s


def _mask_dispatch(pl, work, causal, q_offset, k_offset, block_q, block_k,
                   seq_len, do):
    """Run ``do(mask_causal, mask_tail)`` under the cheapest masks for this
    block class: interior blocks skip the iota/where VPU cost entirely; only
    the causal diagonal band and (statically, when S was padded) the last
    partial K block pay for masks."""
    has_tail = seq_len % block_k != 0
    if causal:
        # a k block is fully below the diagonal iff its last col <= first row
        on_diag = k_offset + block_k - 1 > q_offset

        @pl.when(work & on_diag)
        def _diag():
            do(True, has_tail)

        if has_tail:
            is_tail_blk = k_offset + block_k > seq_len

            @pl.when(work & jnp.logical_not(on_diag) & is_tail_blk)
            def _tail_only():
                do(False, True)

            @pl.when(work & jnp.logical_not(on_diag) &
                     jnp.logical_not(is_tail_blk))
            def _interior():
                do(False, False)
        else:
            @pl.when(work & jnp.logical_not(on_diag))
            def _interior():
                do(False, False)
    elif has_tail:
        is_tail_blk = k_offset + block_k > seq_len

        @pl.when(work & is_tail_blk)
        def _tail():
            do(False, True)

        @pl.when(work & jnp.logical_not(is_tail_blk))
        def _interior():
            do(False, False)
    else:
        @pl.when(work)
        def _all():
            do(False, False)


def _band_first_block(q_offset, window, block_k):
    """The k block that holds the oldest column a q block starting at
    ``q_offset`` sees under ``window``."""
    return jnp.maximum(q_offset - (window - 1), 0) // block_k


def _band_blocks(seq, block_q, block_k, window):
    """k blocks the grid visits a q block under ``window``: from the block of
    its first row's oldest column to the block of its last row's own, at the
    q block where that is most."""
    return max((i + block_q - 1) // block_k
               - max(i - (window - 1), 0) // block_k + 1
               for i in range(0, seq, block_q))


def _attention_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                          m_scr, l_scr, acc_scr, *, sm_scale, causal,
                          block_k, seq_len, num_k, window=None):
    """One (q-block, k-block) grid step. The k axis is the innermost grid
    dimension: K/V blocks stream through VMEM with pallas's automatic
    double-buffered pipelining while the online-softmax state (m, l, acc)
    persists in VMEM scratch across the k sweep. This keeps VMEM usage
    O(block) — independent of S — and overlaps the K/V HBM loads with the
    MXU work (the jax.experimental.pallas.ops.tpu.flash_attention design)."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    block_q = q_ref.shape[1]
    q_offset = qi * block_q
    # under a window the k axis counts from the band's first block
    k_offset = ki * block_k if window is None else \
        (_band_first_block(q_offset, window, block_k) + ki) * block_k

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip k blocks lying fully in the pad region (S padded to a block
    # multiple of max(bq, bk) can add WHOLE k-blocks when bq > bk), and —
    # causal — blocks strictly above the diagonal
    work = k_offset < seq_len
    if causal:
        work &= k_offset <= q_offset + block_q - 1

    if window is not None:
        # the band's edges: the diagonal, and the columns the block's last
        # row no longer sees; a block between them is computed unmasked
        edge = (k_offset + block_k - 1 > q_offset) | \
            (k_offset < q_offset + block_q - window)
        has_tail = seq_len % block_k != 0

    def _do_block(mask_causal, mask_tail):
        q = q_ref[0]                                      # (Bq, D)
        k_blk = k_ref[0]                                  # (Bk, D)
        v_blk = v_ref[0]
        s = _masked_scores(q, k_blk, sm_scale, mask_causal, mask_tail,
                           q_offset, k_offset, block_q, block_k, seq_len,
                           window)
        m_acc = m_scr[:, 0]
        l_acc = l_scr[:, 0]
        m_blk = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_acc, m_blk)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_acc - m_new)
        l_new = l_acc * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_dot_precision(v_blk.dtype))
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    if window is None:
        _mask_dispatch(pl, work, causal, q_offset, k_offset, block_q,
                       block_k, seq_len, _do_block)
    else:
        @pl.when(work & edge)
        def _edge():
            _do_block(True, has_tail)

        @pl.when(work & jnp.logical_not(edge))
        def _inside():      # under the diagonal: never the padded tail
            _do_block(False, False)

    @pl.when(ki == num_k - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)
        # per-row scalar broadcast across the 128-lane axis: TPU tiling
        # requires the last two block dims be (8k, 128)-aligned, so a
        # (bq,)-shaped output is not representable (same layout as
        # pallas.ops.tpu.flash_attention's l/m residuals)
        lse = m_scr[:, 0] + jnp.log(l_safe)
        lse_ref[0] = jnp.broadcast_to(lse[:, None], (block_q, _LANES))


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
               window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, D = q.shape
    Hk = k.shape[1]         # KV heads, each shared by H // Hk query heads
    Dv = v.shape[-1]        # the values may be narrower than the keys
    bq = min(block_q, S)
    bk = min(block_k, S)
    Sp = -(-S // max(bq, bk)) * max(bq, bk)
    if Sp != S:
        pad = [(0, 0), (0, 0), (0, Sp - S), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    qr = q.reshape(B * H, Sp, D)
    kr = k.reshape(B * Hk, Sp, D)
    vr = v.reshape(B * Hk, Sp, Dv)
    num_k = pl.cdiv(Sp, bk)
    if window is None:
        kv_block = lambda b, i, j: (b, j, 0)
    else:
        # the band's blocks alone, from its first one; past the array's end
        # the last block is named again (no new DMA) and not computed
        last = num_k - 1
        num_k = _band_blocks(Sp, bq, bk, window)
        kv_block = lambda b, i, j: (b, jnp.minimum(
            _band_first_block(i * bq, window, bk) + j, last), 0)
    if Hk != H:
        by_group, G = kv_block, H // Hk
        kv_block = lambda b, i, j: by_group(b // G, i, j)
    grid = (B * H, pl.cdiv(Sp, bq), num_k)
    kernel = functools.partial(_attention_fwd_kernel, sm_scale=sm_scale,
                               causal=causal, block_k=bk, seq_len=S,
                               num_k=num_k, window=window)
    scratch = pltpu.VMEM
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), kv_block),
            pl.BlockSpec((1, bk, Dv), kv_block),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sp, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, Sp, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            scratch((bq, _LANES), jnp.float32),   # running max (lane-bcast)
            scratch((bq, _LANES), jnp.float32),   # running sum
            scratch((bq, Dv), jnp.float32),       # output accumulator
        ],
        interpret=interpret,
    )(qr, kr, vr)
    out = out.reshape(B, H, Sp, Dv)[:, :, :S]
    lse = lse[..., 0].reshape(B, H, Sp)[:, :, :S]
    return out, lse


def _dense_bwd(q, k, v, out, lse, g, sm_scale, causal):
    """Recompute-style backward with XLA einsums (fp32 accumulation).
    Materializes the (S, S) score matrix — fine for short sequences."""
    q32 = q.astype(jnp.float32)
    k32 = k.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q32, k32) * sm_scale
    if causal:
        S = q.shape[2]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jnp.exp(s - lse[..., None])                       # softmax probs
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, g32)
    dp = jnp.einsum("bhqd,bhkd->bhqk", g32, v32)
    delta = jnp.sum(g32 * out.astype(jnp.float32), axis=-1, keepdims=True)
    ds = p * (dp - delta) * sm_scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k32)
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q32)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# past this sequence length the backward switches away from the dense
# recompute: its (B, H, S, S) fp32 score tensor at S=4096, B·H=48 would
# already be 3.2 GB of HBM
_BWD_BLOCKWISE_MIN_S = 1024


def _bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref,
                   acc_scr, *, sm_scale, causal, block_k, seq_len, num_k):
    """dq = sum_j ds_ij @ K_j, streamed over k blocks (innermost grid dim)
    with the accumulator in VMEM scratch — same structure as the forward."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    block_q = q_ref.shape[1]
    q_offset = qi * block_q
    k_offset = ki * block_k

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip wholly-pad k blocks, wholly-pad q blocks (their dq is sliced
    # away), and — causal — k blocks strictly above the diagonal
    work = (k_offset < seq_len) & (q_offset < seq_len)
    if causal:
        work &= k_offset <= q_offset + block_q - 1

    def _do(mask_causal, mask_tail):
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        g = g_ref[0]
        s = _masked_scores(q_ref[0], k_blk, sm_scale, mask_causal, mask_tail,
                           q_offset, k_offset, block_q, block_k, seq_len)
        p = jnp.exp(s - lse_ref[0, :, 0][:, None])
        dp = jax.lax.dot_general(g, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=_dot_precision(g.dtype))
        ds = (p * (dp - delta_ref[0, :, 0][:, None]) * sm_scale).astype(
            k_blk.dtype)
        acc_scr[...] += jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_dot_precision(ds.dtype))

    _mask_dispatch(pl, work, causal, q_offset, k_offset, block_q, block_k,
                   seq_len, _do)

    @pl.when(ki == num_k - 1)
    def _fin():
        dq_ref[0] = acc_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale, causal,
                    block_q, seq_len, num_q):
    """dk/dv for one k block, streamed over q blocks (innermost grid dim):
    dv = sum_i P_ij^T @ G_i, dk = sum_i dS_ij^T @ Q_i."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)
    block_k = k_ref.shape[1]
    k_offset = ki * block_k
    q_offset = qi * block_q

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # skip wholly-pad q steps, wholly-pad k blocks (their dk/dv rows are
    # sliced away), and — causal — q blocks strictly above the diagonal
    work = (q_offset < seq_len) & (k_offset < seq_len)
    if causal:
        work &= q_offset + block_q - 1 >= k_offset

    def _do(mask_causal, mask_tail):
        q = q_ref[0]
        v_blk = v_ref[0]
        g = g_ref[0]
        s = _masked_scores(q, k_ref[0], sm_scale, mask_causal, mask_tail,
                           q_offset, k_offset, block_q, block_k, seq_len)
        p = jnp.exp(s - lse_ref[0, :, 0][:, None])
        p_lo = p.astype(g.dtype)
        dv_scr[...] += jax.lax.dot_general(
            p_lo, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_dot_precision(p_lo.dtype))
        dp = jax.lax.dot_general(g, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=_dot_precision(g.dtype))
        ds = (p * (dp - delta_ref[0, :, 0][:, None]) * sm_scale).astype(
            q.dtype)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_dot_precision(ds.dtype))

    _mask_dispatch(pl, work, causal, q_offset, k_offset, block_q, block_k,
                   seq_len, _do)

    @pl.when(qi == num_q - 1)
    def _fin():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _pallas_bwd(q, k, v, out, lse, g, sm_scale, causal, block_q, block_k,
                interpret):
    """Pallas flash backward: dq via a (bh, q, k) grid, dk/dv via a
    (bh, k, q) grid — score strips never leave VMEM (the HBM-bound step of
    the scan-based blockwise backward)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ... import config as _config

    B, H, S, D = q.shape
    # backward-specific block sizes (the bwd kernels' working set is ~3x the
    # forward's per tile, so its optimum differs; r5 sweep on the chip)
    block_q = int(_config.get("MXNET_FLASH_BWD_BLOCK_Q") or block_q)
    block_k = int(_config.get("MXNET_FLASH_BWD_BLOCK_K") or block_k)
    bq = min(block_q, S)
    bk = min(block_k, S)
    Sp = -(-S // max(bq, bk)) * max(bq, bk)
    if Sp != S:
        pad = [(0, 0), (0, 0), (0, Sp - S), (0, 0)]
        q, k, v, out, g = (jnp.pad(x, pad) for x in (q, k, v, out, g))
        lse = jnp.pad(lse, [(0, 0), (0, 0), (0, Sp - S)])
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    BH = B * H
    qr, kr, vr, gr = (x.reshape(BH, Sp, D) for x in (q, k, v, g))
    # lane-broadcast the per-row scalars (same layout rule as the fwd lse)
    lse_b = jnp.broadcast_to(lse.reshape(BH, Sp)[..., None], (BH, Sp, _LANES))
    delta_b = jnp.broadcast_to(delta.reshape(BH, Sp)[..., None],
                               (BH, Sp, _LANES))
    nq = pl.cdiv(Sp, bq)
    nk = pl.cdiv(Sp, bk)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_k=bk, seq_len=S, num_k=nk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, gr, lse_b, delta_b)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=bq, seq_len=S, num_q=nq),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sp, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Sp, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, gr, lse_b, delta_b)

    dq = dq.reshape(B, H, Sp, D)[:, :, :S]
    dk = dk.reshape(B, H, Sp, D)[:, :, :S]
    dv = dv.reshape(B, H, Sp, D)[:, :, :S]
    return dq, dk, dv


def _blockwise_bwd(q, k, v, out, lse, g, sm_scale, causal, block):
    """O(S·D)-memory flash backward: lax.scan over q-blocks recomputing
    (block, S) score strips — never the full (S, S) matrix. Each strip's
    work is two bf16 MXU matmuls + the ds strip, so XLA keeps the MXU busy
    while HBM holds only O(S·D) tensors (the flash-attention backward
    recipe, scan-structured instead of a hand-written Pallas kernel)."""
    B, H, S, D = q.shape
    blk = min(block, S)
    nb = -(-S // blk)
    Sp = nb * blk
    if Sp != S:
        pad = [(0, 0), (0, 0), (0, Sp - S), (0, 0)]
        # zero-padding g is what neutralizes the pad rows: every pad-row
        # contribution (dv via p·g, ds via p·(dp-delta)) carries a factor of
        # g = 0, and the pad rows of dq are sliced away below. The lse pad
        # value is arbitrary — any finite constant works.
        q, out, g = (jnp.pad(x, pad) for x in (q, out, g))
        lse = jnp.pad(lse, [(0, 0), (0, 0), (0, Sp - S)],
                      constant_values=1.0)
    cols = jnp.arange(S)
    # matmul operands stay in the input dtype (bf16 MXU rate) with fp32
    # accumulation via preferred_element_type; only the softmax/ds
    # elementwise math runs fp32
    ein = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)

    def one_block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=2)
        gi = jax.lax.dynamic_slice_in_dim(g, i * blk, blk, axis=2)
        oi = jax.lax.dynamic_slice_in_dim(out, i * blk, blk, axis=2)
        li = jax.lax.dynamic_slice_in_dim(lse, i * blk, blk, axis=2)
        s = ein("bhqd,bhkd->bhqk", qi, k) * sm_scale       # (B,H,blk,S) f32
        rows = i * blk + jnp.arange(blk)
        if causal:
            valid = rows[:, None] >= cols[None, :]
            s = jnp.where(valid[None, None], s, _NEG_INF)
        p = jnp.exp(s - li[..., None])
        p_lo = p.astype(q.dtype)
        dv_i = ein("bhqk,bhqd->bhkd", p_lo, gi)
        dp = ein("bhqd,bhkd->bhqk", gi, v)
        delta = jnp.sum(gi.astype(jnp.float32) * oi.astype(jnp.float32),
                        axis=-1, keepdims=True)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dq_i = ein("bhqk,bhkd->bhqd", ds, k)
        dk_i = ein("bhqk,bhqd->bhkd", ds, qi)
        return dq_i, dk_i, dv_i

    def body(carry, i):
        dk_acc, dv_acc = carry
        dq_i, dk_i, dv_i = one_block(i)
        return (dk_acc + dk_i, dv_acc + dv_i), dq_i

    f32 = jnp.float32
    (dk, dv), dq_blocks = jax.lax.scan(
        body, (jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32)),
        jnp.arange(nb))
    dq = jnp.moveaxis(dq_blocks, 0, 2).reshape(B, H, Sp, D)[:, :, :S]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    return out


def _flash_vjp_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(sm_scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    if q.shape[2] > _BWD_BLOCKWISE_MIN_S:
        if interpret:
            # non-TPU backends: the XLA scan backward — same O(S·D) memory,
            # but orders of magnitude faster than the Pallas interpreter
            return _blockwise_bwd(q, k, v, out, lse, g, sm_scale, causal,
                                  block_q)
        return _pallas_bwd(q, k, v, out, lse, g, sm_scale, causal,
                           block_q, block_k, interpret)
    return _dense_bwd(q, k, v, out, lse, g, sm_scale, causal)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _on_tpu():
    try:
        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover
        return False


# Below this sequence length the COMPILED kernel loses to dense attention on
# the chip: measured fwd+bwd at B64/H12/D64 bf16 (v5e) — S=128 tie
# (5.2 ms both), S=256 dense 6.4 ms vs pallas 8.7 ms, S=512 pallas 15.2 ms
# vs dense 17.2 ms. Below the tile minimum Mosaic also rejects sub-tile dot
# operands outright ("Bad lhs type" at S=16 — BERT-tiny configs). The dense
# path is exact and differentiable; its (S x S) scores stay small at these
# lengths, and the S<=1024 backward is dense recompute either way. The gate
# applies only to the compiled-on-TPU path so interpret-mode tests keep
# exercising the kernel at every size.
_MIN_PALLAS_S = 512
# Below the tile minimum the kernel is also the wrong choice on every OTHER
# backend: the interpreter is orders of magnitude slower than dense XLA, so
# default dispatch goes dense there too — only an explicit interpret=True
# (tests) runs the kernel at sub-tile sizes.
_MIN_KERNEL_S = 128


def _dense_attention(q, k, v, sm_scale, causal, window=None):
    if k.shape[1] != q.shape[1]:    # grouped KV heads: short rows, repeated
        k, v = (jnp.repeat(a, q.shape[1] // a.shape[1], 1) for a in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        # bottom-right aligned for Lq != Lk (the KV-cache decode convention:
        # the LAST query row sees every key), which degenerates to plain
        # tril when Lq == Lk
        S, Sk = q.shape[2], k.shape[2]
        seen = jnp.tril(jnp.ones((S, Sk), bool), k=Sk - S)
        if window is not None:
            seen &= ~jnp.tril(jnp.ones((S, Sk), bool), k=Sk - S - window)
        s = jnp.where(seen, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


@register("flash_attention", jit=True)
def flash_attention(q, k, v, *, causal=False, sm_scale=None,
                    block_q=None, block_k=None, interpret=None, window=None):
    """Fused attention over (B, H, S, D). ``v`` may be (B, H, S, Dv) with
    Dv != D (latent attention's plain form: keys of 192, values of 128): the
    forward kernel takes it as it is; the backward kernels do not, so that
    call has no gradient. ``window`` (with ``causal``): a row sees the last
    ``window`` positions, itself among them, and the grid visits the band's k
    blocks alone; ``k`` and ``v`` may be (B, H_kv, S, ...) with H_kv a divisor
    of H, each KV head read by its group of query heads (module docstring).
    Both are the forward kernel's alone: the backward kernels take neither,
    so such a call has no gradient either (a prefill's; differentiating it
    fails inside ``pallas_call``). Pallas kernel on TPU; interpreter
    (still the same kernel) elsewhere so tests exercise identical code.
    Short sequences (S < 512) on the compiled TPU path take a dense XLA
    route instead — measured faster there, and Mosaic rejects sub-tile
    shapes outright; see _MIN_PALLAS_S above."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    explicit = interpret is not None
    if interpret is None:
        interpret = not _on_tpu()
    # The kernel assumes Lq == Lk throughout (its padding and reshapes take
    # S from q), so ANY cross-length call goes dense; equal lengths below
    # the tile minimum go dense for Mosaic legality / dispatch-cost reasons
    # (advisor r4 + r5 review).
    if window is not None and not causal:
        raise ValueError("flash_attention: a window is the causal mask's")
    if q.shape[2] != k.shape[2] or \
            (not interpret and q.shape[2] < _MIN_PALLAS_S) or \
            (not explicit and q.shape[2] < _MIN_KERNEL_S):
        return _dense_attention(q, k, v, float(sm_scale), bool(causal),
                                window)
    # None = adaptive default (an EXPLICIT block size is always honored):
    # 1024/1024 from S>=16K, 512/1024 below (r5 sweep, heads of 64); heads
    # wider than a lane tile stream more of K a q block, and 1024 rows of q
    # a block pay for it at any S (keys of 192, values of 128, 128 heads on
    # a v5e, PR 32: S=4096 12.0 -> 10.0 ms, S=2048 3.8 -> 3.3)
    long_ctx = q.shape[2] >= _LONG_S
    if block_q is None:
        block_q = _LONG_BLOCK_Q if long_ctx or q.shape[-1] > _LANES \
            else DEFAULT_BLOCK_Q
    if block_k is None:
        block_k = _LONG_BLOCK_K if long_ctx else DEFAULT_BLOCK_K
    args = (q, k, v, float(sm_scale), bool(causal), int(block_q),
            int(block_k), bool(interpret))
    if window is not None or k.shape[1] != q.shape[1]:
        return _flash_fwd(*args, None if window is None
                          else int(window))[0]         # forward only
    if v.shape[-1] != q.shape[-1]:
        return _flash_fwd(*args)[0]                     # forward only
    return _flash(*args)
