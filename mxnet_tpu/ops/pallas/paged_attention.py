"""Attention of a decode step's rows to their sequence's cached context, read
in the paged KV pool through the page table.

The pool is ``(layers, pages, page_size, kv_dim)`` (serving/generate/
kv_cache.py); a sequence's context is the first ``length`` positions of the
pages its table row names. The op returns the *context part* of a two-part
attention — unnormalised output, running maximum, denominator — and the
caller merges it flash-style with the dense part over the step's own rows,
which are not in the pool yet (``ops/nn.py:block_attention``).

On a TPU it is one Pallas kernel. The whole pools stay in HBM, so nothing is
sliced or gathered outside the kernel; the layer to read, the tables and the
lengths are scalar-prefetched. (The layer is an operand, not a constant: a
model's layers then share one traced and lowered kernel per step program:
``_jitted``.) The grid runs over lanes; inside a lane a loop runs over blocks
of pages up to ``ceil(length / page_size)``: each live page of a block is one
DMA from ``pool[layer, page]`` into one of two VMEM slots, and while a block
is computed, a chunk of pages at a time, the next one — the next lane's
first, at a lane's end — is already in flight. Pages past a lane's length are
neither fetched nor computed; the tail of its last page is masked to
``_MASKED``, which underflows to an exactly-zero softmax weight, so whatever a
page holds past the length, and whatever an earlier block left in a slot,
adds exactly nothing (the slots start zeroed: a zero weight times what
uninitialised memory may hold need not be zero). A lane's result depends on
its own rows, pages and length alone — not on the batch, its bucket or its
neighbours.

The rows of a matmul are a lane's L rows times its query heads, each against
its own KV head's columns of the pool's row. Where they are few (12 heads of
one row) one matmul takes the whole row under queries laid block-diagonally,
a head's D columns each; where they are many (32 query heads of 4 rows on 4
KV heads of 128) one matmul takes a column group of whole lane tiles: one KV
head where its width is a multiple of 128, else ``128 // D`` heads, again
block-diagonal. Scores, softmax and accumulation are float32; float32
operands multiply at ``highest``, bfloat16 operands in one exact MXU pass
with the probabilities cast to bfloat16 for the product with the values, as
``block_attention`` does.

A **latent pool** (``v_pool`` None, ``v_dim`` given) is one pool whose row
serves as keys *and* values: latent attention in its absorbed form caches one
compressed row a position (512 + 64 rotary numbers), every query head attends
to that same row (``kv_heads`` 1: 128 heads of one lane row are the 128 rows
of one matmul), and the values are the row's first ``v_dim`` columns. Each
live page is then fetched once, into one buffer, and used twice; the result's
``acc`` is ``v_dim`` wide. The pool's row is whole lane tiles (576 numbers
stored 640 wide, zeros after them: a DMA cannot slice an HBM array whose rows
end inside a tile), and narrower queries are padded with zeros to it.
Everything else is as above.

A **lower bound** a lane (``starts``, beside the lengths and scalar-prefetched
as they are) is where the lane's context begins: the rows attend to positions
``starts[b] .. lengths[b] - 1`` alone (a layer that sees only a window of its
sequence: ``max(0, position - window + 1)``). The loop over blocks then
starts at the page that holds the bound, pages wholly behind it are neither
fetched nor computed, and the head of that first page is masked as the tail
of the last one is. Under a bound the table is a **ring**: logical page p of
a lane lies in entry ``p mod P`` of its row of P entries (a cache group that
keeps a window and one page of slack a sequence, ``serving/generate/
kv_cache.py``), so a lane's positions may pass ``P x page_size`` many times
over while its live pages, at most P, stay distinct entries. A lane's result
still depends on its own rows, pages, bound and length alone. With no bound
nothing above changes: the kernel traces to the text it had.

Off the TPU the same entry point evaluates the same per-lane math as plain
``jax.numpy`` over the lane's pages (``interpret=True`` runs the kernel
itself under the Pallas interpreter: the tests do).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..registry import register
from .flash_attention import _LANES, _NEG_INF as _MASKED, _on_tpu

__all__ = ["paged_attention"]

# a matmul streams its rows past every 128 x 128 tile of K (or V) the MXU
# loads, and the load takes 128 cycles: up to about half as many rows the
# zero blocks of block-diagonal queries ride free, and one matmul over the
# whole row replaces one per column group
_FEW_ROWS = 64
# a block's K (or V) rows in VMEM, fetched together (two slots of each are
# held), and the rows of it one pass of compute takes: a lane's live pages
# are fetched a block at a time and computed a chunk at a time, so a short
# lane computes few masked positions and a long one has much in flight.
# Swept on a v5e at both decode families' shapes (PERF.md, PR 29): chunks of
# 256 positions of a 768-wide float32 row and 512 of a 512-wide bfloat16 one
_BLOCK_BYTES = 1536 << 10
_CHUNK_BYTES = 768 << 10


def _precision(dtype):
    return (lax.Precision.DEFAULT if dtype == jnp.bfloat16
            else lax.Precision.HIGHEST)


def _group_width(kv_dim, head_dim, rows):
    """Columns of the pool's row one matmul takes: whole KV heads, whole
    lane tiles; the whole row where all heads' ``rows`` are few."""
    if rows <= _FEW_ROWS:
        return kv_dim
    if head_dim % _LANES == 0:
        return head_dim
    if _LANES % head_dim == 0 and kv_dim % _LANES == 0:
        return _LANES
    return kv_dim


def _kernel(layer_ref, lengths_ref, *refs,
            page_size, pages_per_block, pages_per_chunk, pages_per_seq,
            batch, sm_scale, v_dim, bounded=False):
    if bounded:     # a lower bound a lane, and its table a ring
        starts_ref, tables_ref, q_ref, *refs = refs
    else:
        tables_ref, q_ref, *refs = refs
    if v_dim is None:
        k_hbm, v_hbm, acc_ref, m_ref, l_ref, k_buf, v_buf, sems, state = refs
    else:       # a latent pool: its row is the keys, its first columns the values
        k_hbm, acc_ref, m_ref, l_ref, k_buf, sems, state = refs
    b = pl.program_id(0)
    n_groups, rows, width = q_ref.shape
    block = pages_per_block * page_size      # positions a block of DMAs
    span = pages_per_chunk * page_size       # positions a pass of compute
    layer = layer_ref[0]
    length = lengths_ref[b]
    if bounded:
        # blocks count from the page that holds the lane's bound
        first_page = lambda lane: starts_ref[lane] // page_size
        n_blocks = pl.cdiv(pl.cdiv(length, page_size) - first_page(b),
                           pages_per_block)
    else:
        n_blocks = pl.cdiv(length, block)

    def live_pages(lane, i):
        """Pages of block ``i`` of ``lane`` that hold a position it sees."""
        last = pl.cdiv(lengths_ref[lane], page_size)
        if bounded:
            last = last - first_page(lane)
        return jnp.minimum(last - i * pages_per_block, pages_per_block)

    def page_copies(page, slot, j):
        k_copy = pltpu.make_async_copy(k_hbm.at[layer, page],
                                       k_buf.at[slot, j], sems.at[0, slot])
        if v_dim is not None:
            return (k_copy,)
        return (k_copy,
                pltpu.make_async_copy(v_hbm.at[layer, page],
                                      v_buf.at[slot, j], sems.at[1, slot]))

    def start(lane, i, slot):
        def one(j, _):
            if bounded:
                page = tables_ref[lane * pages_per_seq + (
                    first_page(lane) + i * pages_per_block + j)
                    % pages_per_seq]
            else:
                page = tables_ref[lane * pages_per_seq
                                  + i * pages_per_block + j]
            for c in page_copies(page, slot, j):
                c.start()
            return ()
        lax.fori_loop(0, live_pages(lane, i), one, ())

    def wait(lane, i, slot):
        def one(j, _):      # a wait takes its page's size off the semaphore
            for c in page_copies(0, slot, j):
                c.wait()
            return ()
        lax.fori_loop(0, live_pages(lane, i), one, ())

    @pl.when(b == 0)
    def _first_lane():
        k_buf[...] = jnp.zeros_like(k_buf)
        if v_dim is None:
            v_buf[...] = jnp.zeros_like(v_buf)
        state[0] = 0        # the slot the next block to compute lies in
        state[1] = 0        # whether any block's DMAs have been started

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _MASKED)
    l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when((n_blocks > 0) & (state[1] == 0))
    def _first_block():
        start(b, 0, state[0])
        state[1] = 1

    def next_lane():
        return lax.while_loop(
            lambda n: (n < batch)
            & (lengths_ref[jnp.minimum(n, batch - 1)] == 0),
            lambda n: n + 1, b + 1)

    def body(i, _):
        slot = state[0]
        last = i + 1 == n_blocks
        lane = lax.cond(last, next_lane, lambda: b)

        @pl.when(lane < batch)
        def _prefetch():
            start(jnp.minimum(lane, batch - 1), jnp.where(last, 0, i + 1),
                  1 - slot)

        wait(b, i, slot)

        def chunk(c, _):
            at = pl.ds(c * pages_per_chunk, pages_per_chunk)
            pos = i * block + c * span + lax.broadcasted_iota(
                jnp.int32, (rows, span), 1)
            if bounded:
                pos = pos + first_page(b) * page_size
                seen = (pos < length) & (pos >= starts_ref[b])
            else:
                seen = pos < length
            for g in range(n_groups):
                cols = slice(g * width, (g + 1) * width)
                k = k_buf[slot, at, :, cols].reshape(span, width)
                v = k[:, :v_dim] if v_dim is not None else \
                    v_buf[slot, at, :, cols].reshape(span, width)
                q = q_ref[g]
                s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=_precision(q.dtype)) * sm_scale
                s = jnp.where(seen, s, _MASKED)
                m_old = m_ref[g]
                m_new = jnp.maximum(m_old, s.max(-1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m_old - m_new)
                l_ref[g] = alpha * l_ref[g] + p.sum(-1, keepdims=True)
                acc_ref[g] = alpha * acc_ref[g] + lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=_precision(v.dtype))
                m_ref[g] = m_new
            return ()

        lax.fori_loop(0, pl.cdiv(live_pages(b, i), pages_per_chunk), chunk,
                      ())
        state[0] = 1 - slot
        return ()

    lax.fori_loop(0, n_blocks, body, ())


def _pallas_context(qg, k_pool, v_pool, tables, lengths, layer, starts=None,
                    *, sm_scale, v_dim, interpret):
    """``qg`` (B, groups, rows, width), the queries by column group: the
    kernel's (acc (B, groups, rows, width; ``v_dim`` of a latent pool), m, l
    (B, groups, rows, 1))."""
    B, n_groups, rows, width = qg.shape
    out_width = width if v_dim is None else v_dim
    pools = (k_pool,) if v_dim is not None else (k_pool, v_pool)
    _, _, page_size, kv_dim = k_pool.shape
    P = tables.shape[1]
    page_bytes = page_size * kv_dim * k_pool.dtype.itemsize
    # a power of two of pages a chunk (its positions tile), chunks a block
    pages_per_chunk = 1 << max(
        0, min(P, _CHUNK_BYTES // page_bytes).bit_length() - 1)
    pages_per_block = pages_per_chunk * max(
        1, min(P, _BLOCK_BYTES // page_bytes) // pages_per_chunk)
    kernel = functools.partial(
        _kernel, page_size=page_size, pages_per_block=pages_per_block,
        pages_per_chunk=pages_per_chunk, pages_per_seq=P, batch=B,
        sm_scale=sm_scale, v_dim=v_dim, bounded=starts is not None)
    bounds = () if starts is None else (starts,)
    lane = lambda b, *_: (b, 0, 0, 0)     # and the prefetched scalars
    buf = pltpu.VMEM((2, pages_per_block, page_size, kv_dim), k_pool.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + len(bounds),
            grid=(B,),
            in_specs=[pl.BlockSpec((None, n_groups, rows, width), lane)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=[pl.BlockSpec((None, n_groups, rows, out_width), lane),
                       pl.BlockSpec((None, n_groups, rows, 1), lane),
                       pl.BlockSpec((None, n_groups, rows, 1), lane)],
            scratch_shapes=[buf] * len(pools)
            + [pltpu.SemaphoreType.DMA((len(pools), 2)),
               pltpu.SMEM((2,), jnp.int32)]),
        out_shape=[
            jax.ShapeDtypeStruct((B, n_groups, rows, out_width), jnp.float32),
            jax.ShapeDtypeStruct((B, n_groups, rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, n_groups, rows, 1), jnp.float32)],
        # lanes in order: a lane's last block starts the next lane's first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(layer.reshape(1), lengths, *bounds, tables.reshape(-1), qg, *pools)


def _dense_context(qg, k_pool, v_pool, tables, lengths, layer, starts=None,
                   *, sm_scale, v_dim):
    """The kernel's result as plain ``jax.numpy``: each lane's pages read
    through its table, one masked pass over all of them."""
    B, n_groups, rows, width = qg.shape
    P, page_size = tables.shape[1], k_pool.shape[2]
    C = P * page_size
    k = k_pool[layer][tables].reshape(B, C, n_groups, width)
    v = k[..., :v_dim] if v_dim is not None else \
        v_pool[layer][tables].reshape(B, C, n_groups, width)
    prec = _precision(qg.dtype)
    s = jnp.einsum("bgrw,bcgw->bgrc", qg, k, precision=prec,
                   preferred_element_type=jnp.float32) * sm_scale
    pos = jnp.arange(C, dtype=jnp.int32)[None, :]
    if starts is None:
        seen = pos < lengths[:, None]
    else:
        # a ring: entry e holds the one logical page congruent to e among
        # the P from the bound's page on
        first = (starts // page_size)[:, None]
        page = first + (pos // page_size - first) % P
        pos = page * page_size + pos % page_size
        seen = (pos < lengths[:, None]) & (pos >= starts[:, None])
    s = jnp.where(seen[:, None, None, :], s, _MASKED)
    m = s.max(-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(seen[:, None, None, :], p, 0.0)   # a lane of length 0
    acc = jnp.einsum("bgrc,bcgw->bgrw", p.astype(v.dtype), v, precision=prec,
                     preferred_element_type=jnp.float32)
    return acc, m, p.sum(-1, keepdims=True)


def _attend(q, k_pool, v_pool, tables, lengths, layer, starts=None, *,
            heads, kv_heads, sm_scale, v_dim, context):
    """:func:`paged_attention` over arrays alone: the queries laid out by
    column group, ``context`` (the kernel or the plain expression) over them,
    and its result back by head."""
    B, L, _ = q.shape
    kv_dim = k_pool.shape[-1]
    D = kv_dim // kv_heads
    if v_dim is not None and q.shape[-1] < heads * D:
        # queries of a latent narrower than the pool's padded row
        q = q.reshape(B, L, heads, -1)
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, D - q.shape[-1]),)) \
            .reshape(B, L, heads * D)
    G = heads // kv_heads
    width = _group_width(kv_dim, D, heads * L)
    per = width // D                    # KV heads a column group
    n_groups = kv_dim // width
    # rows of a group: (KV head in the group, query head of it, row of the
    # step), each against its own head's D columns of the group
    qh = q.reshape(B, L, n_groups, per, G, D).transpose(0, 2, 3, 4, 1, 5)
    eye = jnp.eye(per, dtype=q.dtype)
    qg = (qh[:, :, :, :, :, None, :] * eye[:, None, None, :, None]) \
        .reshape(B, n_groups, per * G * L, width)
    rows = per * G * L
    tile = 8 * 4 // q.dtype.itemsize    # sublanes of one tile of q's dtype
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, -rows % tile), (0, 0)))
    acc, m, l = context(qg, k_pool, v_pool, tables.astype(jnp.int32),
                        lengths.astype(jnp.int32),
                        jnp.asarray(layer, jnp.int32),
                        *(() if starts is None
                          else (starts.astype(jnp.int32),)),
                        sm_scale=sm_scale, v_dim=v_dim)
    # back to (B, L, heads, ...): a head's own D columns of its group (of a
    # latent pool, one group of one head: all ``v_dim`` columns)
    Dv = acc.shape[-1] // per
    acc = acc[:, :, :rows].reshape(B, n_groups, per, G, L, per, Dv)
    own = jnp.arange(per)
    acc = acc[:, :, own, :, :, own]     # (per, B, groups, G, L, Dv)
    acc = acc.transpose(1, 4, 2, 0, 3, 5).reshape(B, L, heads, Dv)

    def rows_to_heads(x):
        x = x[:, :, :rows, 0].reshape(B, n_groups, per, G, L)
        return x.transpose(0, 4, 1, 2, 3).reshape(B, L, heads)

    return acc, rows_to_heads(m), rows_to_heads(l)


@functools.lru_cache(maxsize=None)
def _jitted(heads, kv_heads, sm_scale, v_dim, context):
    """One jitted :func:`_attend` per configuration: a model's layers (and
    its step programs' traces) then share one trace of the kernel a shape.
    Tracing and lowering a kernel per layer took more of an endpoint's
    warm-up than all the rest of it."""
    return jax.jit(functools.partial(
        _attend, heads=heads, kv_heads=kv_heads, sm_scale=sm_scale,
        v_dim=v_dim, context=context))


_INTERPRETED = functools.partial(_pallas_context, interpret=True)
_COMPILED = functools.partial(_pallas_context, interpret=False)


@register("paged_attention", jit=True)
def paged_attention(q, k_pool, v_pool, tables, lengths, layer, starts=None,
                    *, heads, kv_heads=None, sm_scale=None, v_dim=None,
                    interpret=None):
    """The context part of a decode step's attention, through the page table.

    ``q`` (B, L, heads*D): the step's L rows a lane; ``k_pool``/``v_pool``
    the whole pools ``(layers, pages, page_size, kv_heads*D)``; ``tables``
    (B, P) int32 physical page ids; ``lengths`` (B,) int32: every row of lane
    b attends to positions ``0..lengths[b]-1`` of its pages and to nothing
    else; ``layer`` (an int or an int32 scalar) the layer of the pools to
    read. Query head h reads KV head ``h // (heads / kv_heads)``. A latent
    pool is ``k_pool`` alone (``v_pool`` None, ``kv_heads`` 1): its row is
    every head's key and its first ``v_dim`` columns their value.
    ``starts`` (B,) int32, if given: lane b attends to positions
    ``starts[b]..lengths[b]-1`` alone, and ``tables`` is a ring, logical page
    p in entry ``p % P`` (module docstring).

    Returns ``(acc (B, L, heads, D; ``v_dim`` of a latent pool), m (B, L,
    heads), l (B, L, heads))``, all
    float32: with scores ``s = q.k * sm_scale`` (default ``1/sqrt(D)``),
    ``m = max s`` (``-1e30`` for a lane of length 0), ``l = sum exp(s - m)``
    and ``acc = sum exp(s - m) v``. ``interpret``: None picks the compiled
    kernel on a TPU and the plain expression elsewhere; True runs the kernel
    under the Pallas interpreter, False compiles it."""
    heads = int(heads)
    kv_heads = heads if kv_heads is None else int(kv_heads)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(k_pool.shape[-1] // kv_heads)
    if interpret is None:
        context = _COMPILED if _on_tpu() else _dense_context
    else:
        context = _INTERPRETED if interpret else _COMPILED
    if (v_pool is None) != (v_dim is not None):
        raise ValueError("paged_attention: a latent pool is k_pool alone "
                         "with v_dim stated; K and V pools state none")
    return _jitted(heads, kv_heads, float(sm_scale),
                   None if v_dim is None else int(v_dim), context)(
        q, k_pool, v_pool, tables, lengths, layer,
        *(() if starts is None else (starts,)))
