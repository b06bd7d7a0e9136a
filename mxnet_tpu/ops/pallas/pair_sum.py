"""The sum of each row's (row, expert) pair results, read where the pairs
sorted to: ``out[t] = sum_k w[t, k] * y[pos[t, k]]``.

A routed layer's grouped products write one float32 result row a pair, the
pairs sorted by expert and, within an expert, by row
(``ops/nn.py:expert_ffn``). This gathers each row's ``top_k`` result rows
back and adds them, weighted, in the row's own order ``k = 0, 1, ...``, in
float32, and rounds the sum to the result's dtype once. A pair routed to an
expert held elsewhere (or a padded row's) adds exactly nothing and its
result is never read: rows the products did not compute may hold anything,
NaN too.

On a TPU it is one Pallas kernel over tiles of rows; the results stay in
HBM. A DMA from a float32 array moves whole tiles of 8 rows, so the kernel
fetches the 8-row granules that hold a tile's pairs: because the pairs sort
by expert and then by row, a tile's pairs in one expert are one run of
results, and a run is its few granules, fetched into the tile's VMEM buffer;
a granule that two tiles' runs share is read once and copied across. Granule
0 of the buffer is zeros, and a dead pair reads it at a weight of 0. While a
tile is summed, a row at a time from the buffer, the next tile's DMAs go out
in parts between the sums. The results are read once and the rows written
once: nothing the size of the results is written besides them. Off the TPU
the same entry point is the same sum as plain ``jax.numpy``, a gather a
``k`` (the tests run the kernel under the Pallas interpreter).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _LANES, _on_tpu

__all__ = ["pair_sum"]

_GRANULE = 8            # rows of float32 one DMA moves at a time
_SMEM_BLOCK = 1024      # int32 a block of a tile's pairs in SMEM comes in
_PARTS = 8              # the next tile's DMAs go out in parts between sums
# VMEM for the two tiles' granule buffers: a tile of 256 rows of mellum2's
# 8 pairs over 64 experts at 2,304 wide takes 377 granules at most
_BUFFER_BYTES = 60 << 20


def _granules(rows, top_k, groups):
    """Buffer granules a tile of ``rows`` needs at most: a run of c pairs
    spans ``ceil(c / 8) + 1`` granules at most, and the zero granule."""
    return (rows * top_k + 7 * groups) // _GRANULE + groups + 1


def _tile_rows(T, top_k, groups, H):
    """Rows a tile: ``T``, or the most, a power of two dividing it whose
    pairs fill whole SMEM blocks of 1,024, whose two buffers fit
    ``_BUFFER_BYTES``; None where none does."""
    fits = lambda r: 2 * _granules(r, top_k, groups) * _GRANULE * H * 4 \
        <= _BUFFER_BYTES
    if fits(T):
        return T
    rows = 1 << (T.bit_length() - 1)
    while rows >= _GRANULE:
        if T % rows == 0 and rows * top_k % _SMEM_BLOCK == 0 and fits(rows):
            return rows
        rows //= 2
    return None


def _kernel(first_ref, count_ref, dst_ref, kept_ref, total_ref, local_ref,
            w_ref, y_hbm, out_ref, buf, acc, sems, *, rows, top_k, groups):
    i = pl.program_id(0)
    slot = i % 2
    at8 = lambda g: pl.ds(pl.multiple_of(g * _GRANULE, _GRANULE), _GRANULE)
    copy = lambda src, s, at: pltpu.make_async_copy(src, buf.at[s, at8(at)],
                                                    sems.at[s])

    def start(tile, s, groups_from, groups_to):
        def run(e, _):
            at = tile * groups + e
            kept = kept_ref[at]

            @pl.when(kept >= 0)     # the previous tile's last granule
            def _shared():
                copy(buf.at[1 - s, at8(kept)], s, dst_ref[at]).start()

            def one(j, _):
                copy(y_hbm.at[at8(first_ref[at] + j)], s,
                     dst_ref[at] + j).start()
                return ()
            return lax.fori_loop((kept >= 0).astype(jnp.int32),
                                 count_ref[at], one, ())
        lax.fori_loop(groups_from, groups_to, run, ())

    @pl.when(i == 0)
    def _first_tile():
        buf[:, :_GRANULE] = jnp.zeros_like(buf[:, :_GRANULE])
        start(0, 0, 0, groups)

    def wait(j, _):     # a wait takes one granule's bytes off the semaphore
        copy(y_hbm.at[at8(0)], slot, 0).wait()
        return ()
    lax.fori_loop(0, total_ref[i], wait, ())

    def row(r, _):
        at = r * top_k
        a = None
        for k in range(top_k):
            term = w_ref[at + k] * buf[slot, pl.ds(local_ref[at + k], 1), :]
            a = term if a is None else a + term
        acc[pl.ds(r, 1), :] = a
        return ()

    # a part of the next tile's granules, then a part of this tile's rows:
    # this tile's granules are all here, so a granule both tiles' runs share
    # is copied across and not read again, and the DMAs go out between
    # the sums, not before them
    parts = _PARTS if rows % _PARTS == 0 else 1

    def part(c, _):
        @pl.when(i + 1 < pl.num_programs(0))
        def _next_tile():
            start(i + 1, 1 - slot, c * groups // parts,
                  (c + 1) * groups // parts)
        return lax.fori_loop(c * (rows // parts), (c + 1) * (rows // parts),
                             row, ())
    lax.fori_loop(0, parts, part, ())
    out_ref[...] = acc[...].astype(out_ref.dtype)


def _plan(pos, group, w, sizes, rows):
    """Per tile and group: the first granule of the tile's run, its
    granules, where they go in the buffer, where the first lies in the
    previous tile's buffer if that tile's run ends in it (else -1); per tile
    the granules in all; per pair its row in the buffer and its weight (0
    for a dead pair)."""
    T, top_k = group.shape
    E = sizes.shape[0]
    n = T // rows
    by_tile = lambda a: a.reshape(n, rows * top_k)
    mine = by_tile(group)[:, :, None] == jnp.arange(E, dtype=jnp.int32)
    count = mine.sum(1, dtype=jnp.int32)                        # (n, E)
    # a tile's run in each group starts past the earlier tiles' pairs
    start = (jnp.cumsum(sizes) - sizes)[None, :] + jnp.cumsum(count, 0) \
        - count
    first = start // _GRANULE
    last = (start + count - 1) // _GRANULE
    granules = jnp.where(count > 0, last - first + 1, 0)
    dst = 1 + jnp.cumsum(granules, 1) - granules    # granule 0 is zeros
    before = lambda a: jnp.pad(a, ((1, 0), (0, 0)))[:-1]
    kept = jnp.where((count > 0) & (before(count) > 0)
                     & (first == before(last)), before(dst + granules - 1),
                     -1)
    shift = _GRANULE * (dst - first)
    live = by_tile(group) < E
    local = jnp.where(live, by_tile(pos) + jnp.where(
        mine, shift[:, None, :], 0).sum(2, dtype=jnp.int32), 0)
    return (first.reshape(-1), granules.reshape(-1), dst.reshape(-1),
            kept.reshape(-1), granules.sum(1), local.reshape(-1),
            jnp.where(live, by_tile(w), 0.0).reshape(-1))


def _pallas_sum(y, pos, group, w, sizes, out_dtype, rows, interpret):
    T, top_k = group.shape
    H = y.shape[1]
    E = sizes.shape[0]
    granules = _granules(rows, top_k, E)
    tile = lambda i, *_: (i,)
    vmem = (2 * granules * _GRANULE + rows) * H * 4 \
        + 2 * rows * H * jnp.dtype(out_dtype).itemsize
    out = pl.pallas_call(
        functools.partial(_kernel, rows=rows, top_k=top_k, groups=E),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(T // rows,),
            in_specs=[pl.BlockSpec((rows * top_k,), tile,
                                   memory_space=pltpu.SMEM)] * 2
            + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((rows, H), lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, granules * _GRANULE, H), jnp.float32),
                pltpu.VMEM((rows, H), jnp.float32),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((T, H), out_dtype),
        # tiles in order: a tile starts the next tile's DMAs
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem + (8 << 20)),
        interpret=interpret,
        name="pair_sum",
    )(*_plan(pos, group, w, sizes, rows), y)
    # in a loop (a long prompt's blocks) XLA would fuse the kernel into the
    # update of the loop's result, and a kernel fused so may hold only 16
    # MiB of VMEM
    return lax.optimization_barrier(out)


@functools.lru_cache(maxsize=None)
def _jitted(out_dtype, rows):
    """One jitted kernel call a tiling: a model's routed layers, and its
    programs whose passes have the same shape, then share one trace of the
    kernel (traced for every layer of every program, it took five times as
    long as the rest of a mellum2 endpoint's warm-up trace on a v5e host)."""
    return jax.jit(functools.partial(_pallas_sum, out_dtype=out_dtype,
                                     rows=rows, interpret=False))


def _dense_sum(y, pos, group, w, sizes, out_dtype):
    live = group < sizes.shape[0]
    # XLA's CPU backend fuses some of these products into the sum as
    # multiply-adds, which round once where the kernel rounds twice, and
    # which it fuses depends on what surrounds the pass (a prompt's blocks or
    # not): an OR with a zero it cannot see (a pass counts fewer than 2**31
    # pairs) keeps each product rounded on its own
    zero = sizes.sum() >> 31
    acc = None
    for k in range(pos.shape[1]):
        bits = lax.bitcast_convert_type(y[pos[:, k]] * w[:, k, None],
                                        jnp.int32) | zero
        term = jnp.where(live[:, k, None],
                         lax.bitcast_convert_type(bits, jnp.float32), 0.0)
        acc = term if acc is None else acc + term
    return acc.astype(out_dtype)


def pair_sum(y, pos, group, w, sizes, out_dtype):
    """``y`` (pairs, H) float32: the results of (row, expert) pairs, each
    group's pairs in row order, the groups one after another, ``sizes`` (G,)
    pairs a group, the rows past ``sizes.sum()`` not computed; ``pos``
    (T, top_k) int32: where row t's k-th pair lies in ``y``; ``group``
    (T, top_k) int32: its group, G or more for a pair that adds nothing;
    ``w`` (T, top_k) float32 weights. Returns (T, H) ``out_dtype``: each
    row's weighted pairs summed in float32 in k order, rounded once. The
    kernel on a TPU; the plain expression elsewhere, or where no tile
    fits."""
    T, top_k = group.shape
    P, H = y.shape
    pos, group = pos.astype(jnp.int32), group.astype(jnp.int32)
    sizes = sizes.astype(jnp.int32)
    rows = _tile_rows(T, top_k, sizes.shape[0], H)
    if not _on_tpu() or rows is None or H % _LANES or P % _GRANULE:
        return _dense_sum(y, pos, group, w, sizes, out_dtype)
    return _jitted(jnp.dtype(out_dtype), rows)(
        y, pos, group, w.astype(jnp.float32), sizes)
