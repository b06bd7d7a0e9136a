"""Paged KV cache: preallocated on-device block pools for autoregressive decode.

Contiguous per-sequence KV buffers force the classic serving dilemma:
reserve max_seq_len per sequence (wasting most of it on short outputs) or
reallocate as sequences grow (fragmenting HBM and recompiling shapes). The
paged layout decouples the two — the pool preallocates a fixed grid of
fixed-size pages ONCE, per-sequence page tables map logical positions to
physical pages, and the decode executables take the pool arrays as
*arguments* (the params-as-arguments lesson from PERF.md round 4), so the
compiled prefill/decode-step programs are independent of pool contents and
of which sequence owns which page.

Layout: ``(num_layers, num_pages, page_size, kv_dim)`` per pool (one for K,
one for V). A **latent pool** (``latent=True``) is one array and not two: its
row is a position's compressed latent (latent attention caches 512 + 64
rotary numbers a position a layer, shared by all heads), which the decode
step's attention reads once and uses as the keys and, in its first columns,
as the values; it is allocated, written, donated and compacted once, and
everything below holds for it with ``arrays`` one long. Its rows lie on whole
lane tiles: ``kv_dim`` 576 is stored 640 wide, the last 64 columns zero (the
kernel's DMA takes a page's rows whole, and Mosaic slices an array in HBM
only along whole tiles of 128 lanes; XLA's own tiled layout would give a
576-wide row the same 640 in HBM, so the chip holds no byte more, and the
array now says what it holds). The writes pad a row, the kernel its queries.
**Page 0 is reserved as a scratch page** and never allocated:
a step's writes for padded/invalid rows are routed to it, and padded
page-table entries name it. A decode step reads the pool where it lies
(``ops/pallas/paged_attention``): each lane's pages through its table, up to
the lane's own length and no further, so page 0 and pages past the length
are never fetched, and what a lane's last page holds past its length is
masked to an exactly-zero softmax weight before it can touch a real row
(``_MASKED`` underflow), which is the property the batched-vs-serial bitwise
decode oracle rests on.

Host-side management (alloc/free/defrag, counters, the memstats holder) is
in :class:`PagedKVPool`; the jit-side write helpers (:func:`write_prefill`,
:func:`write_step`) are pure functions traced into the compiled executables;
the read is the attention kernel's. The writes are
``dynamic_update_slice`` under a loop, not an advanced-index scatter: the
TPU compiler performs them in the pool's own layout, so with the pools
donated a step or a prefill touches only the rows it writes. The scatter
form costs four pool-sized relayout copies per executable
(:func:`write_step`).
"""
from __future__ import annotations

import math
import threading
from typing import Dict, List, Optional

import numpy as onp

from ... import config as _config
from ... import telemetry as _telemetry
from ...base import MXNetError
from ...resilience import faults as _faults
from ..errors import KVPoolExhausted

__all__ = ["PagedKVPool", "KVPoolExhausted", "write_prefill", "write_step"]

_LANES = 128        # a latent pool's row is whole tiles of this many columns

_POOL_PAGES = _telemetry.gauge(
    "mxtpu_kv_pool_pages",
    "Usable pages preallocated in one paged KV pool (page 0, the scratch "
    "page for masked writes, is excluded).",
    labelnames=("pool",))
_IN_USE = _telemetry.gauge(
    "mxtpu_kv_pages_in_use",
    "Pages currently owned by live sequence page tables.",
    labelnames=("pool",))
_ALLOCATED = _telemetry.counter(
    "mxtpu_kv_pages_allocated_total",
    "Pages handed out by reserve() over the pool's lifetime.",
    labelnames=("pool",))
_FREED = _telemetry.counter(
    "mxtpu_kv_pages_freed_total",
    "Pages returned by free() (sequence finished/cancelled/failed).",
    labelnames=("pool",))
_EXHAUSTED = _telemetry.counter(
    "mxtpu_kv_pool_exhausted_total",
    "reserve() calls refused for lack of free pages; the scheduler keeps "
    "the sequence queued, so a climbing rate means the pool is sized below "
    "the offered concurrency * sequence length.",
    labelnames=("pool",))
_DEFRAGS = _telemetry.counter(
    "mxtpu_kv_defrags_total",
    "Compaction passes run on the pool.", labelnames=("pool",))
_DEFRAG_MOVED = _telemetry.counter(
    "mxtpu_kv_defrag_pages_moved_total",
    "Physical pages relocated by compaction passes.", labelnames=("pool",))


# ---------------------------------------------------------------------------
# jit-side helpers: pure functions over pool arrays, traced into the
# prefill / decode-step executables
# ---------------------------------------------------------------------------
def write_prefill(pool, vals, table_row, length, page_size: int):
    """Write one sequence's prefill projections into its pages, in place.

    ``pool`` (num_layers, num_pages, page_size, kv_dim); ``vals``
    (num_layers, S, kv_dim) — per-position K (or V) for positions 0..S-1;
    ``table_row`` (P,) int32 physical page ids (0-padded); ``length`` scalar
    int32 — positions >= length are padding and are not written: in the
    sequence's last page they keep what the page held, and pages wholly
    past ``length`` are skipped. ``pool`` and ``vals`` may be matching
    tuples (K and V): one loop then carries both.

    One ``(num_layers, 1, page_size, kv_dim)`` block per live page, read
    with ``dynamic_slice`` and written back with ``dynamic_update_slice``
    (see :func:`write_step` for why not a scatter). S need not be a
    multiple of ``page_size``, nor reach it."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    vals = _as_wide_as(pool, vals)
    S = jax.tree.leaves(vals)[0].shape[1]
    n_pages = -(-S // page_size)
    pad = n_pages * page_size - S
    if pad:
        vals = jax.tree.map(
            lambda v: jnp.pad(v, ((0, 0), (0, pad), (0, 0))), vals)
    length = jnp.minimum(length, S)     # the loop stays inside the bucket
    lane = jnp.arange(page_size, dtype=jnp.int32)[None, None, :, None]

    def body(j, pools):
        start = (0, table_row[j], 0, 0)
        keep = j * page_size + lane < length

        def put(p, v):
            new = lax.dynamic_slice_in_dim(v, j * page_size, page_size, 1)
            old = lax.dynamic_slice(
                p, start, (p.shape[0], 1, page_size, p.shape[3]))
            return lax.dynamic_update_slice(
                p, jnp.where(keep, new[:, None], old), start)

        return jax.tree.map(put, pools, vals)

    return lax.fori_loop(0, -(-length // page_size), body, pool)


def write_step(pool, vals, tables, positions, valid, page_size: int):
    """Write one decode step's new K (or V) rows per sequence, in place.

    ``vals`` (num_layers, B, kv_dim); ``tables`` (B, P) int32;
    ``positions`` (B,) int32 — the lane each row's new token occupies;
    ``valid`` (B,) bool — padding rows route to scratch page 0 (where
    duplicate slots land in row order; nothing ever reads page 0
    unmasked). ``pool`` and ``vals`` may be matching tuples (K and V): one
    loop then carries both.

    A step of L rows a sequence passes ``vals`` (num_layers, B, L, kv_dim)
    and ``positions`` (B, L): a block of consecutive positions that starts
    on a multiple of L, where L divides ``page_size``, so that it lies in
    one page and is one slice. ``valid`` is then the sequence's commit flag:
    a denoising step's rows go to the scratch page too.

    One ``dynamic_update_slice`` of a ``(num_layers, 1, L, kv_dim)`` slab per
    sequence, under a loop the pool passes through in its own layout. The
    advanced-index scatter ``pool.at[:, page, slot, :].set(vals)`` writes
    the same rows, but the TPU compiler gives its scatter a layout with the
    scattered dimensions major and relayouts the whole pool into it and
    back — two pool-sized copies per pool in every executable, donated or
    not (tests/test_tpu_compile.py holds the compiled program to none)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    B = tables.shape[0]
    vals = _as_wide_as(pool, vals)
    if positions.ndim == 1:         # one row a sequence: a block of one
        positions = positions[:, None]
        vals = jax.tree.map(lambda v: v[:, :, None], vals)
    first = positions[:, 0]
    page = tables[jnp.arange(B), first // page_size]
    page = jnp.where(valid, page, 0)
    slot = first % page_size

    def body(i, pools):
        start = (0, page[i], slot[i], 0)

        def put(p, v):
            rows = lax.dynamic_slice_in_dim(v, i, 1, 1)    # (layers, 1, L, kv)
            return lax.dynamic_update_slice(p, rows, start)

        return jax.tree.map(put, pools, vals)

    return lax.fori_loop(0, B, body, pool)


def _as_wide_as(pool, vals):
    """``vals`` with zero columns up to their pool's row (a latent pool's
    rows are whole lane tiles; K and V rows are their pools' width already)."""
    import jax
    import jax.numpy as jnp
    return jax.tree.map(
        lambda p, v: v if v.shape[-1] == p.shape[3] else jnp.pad(
            v, [(0, 0)] * (v.ndim - 1) + [(0, p.shape[3] - v.shape[-1])]),
        pool, vals)


# ---------------------------------------------------------------------------
# host-side pool management
# ---------------------------------------------------------------------------
class PagedKVPool:
    """Preallocated paged KV storage plus its free-list allocator.

    Thread-safety: all mutators take the internal lock, but array
    replacement (``update_arrays``) and ``defrag`` follow the serving
    single-dispatcher rule — only the decode worker thread runs them, so a
    step never races a compaction.
    """

    def __init__(self, name: str, num_layers: int, kv_dim: int,
                 max_seq_len: int, page_size: Optional[int] = None,
                 num_pages: Optional[int] = None, dtype="float32",
                 device=None, latent: bool = False):
        import jax
        import jax.numpy as jnp
        if page_size is None:
            page_size = int(_config.get("MXNET_KV_PAGE_SIZE"))
        if num_pages is None:
            num_pages = int(_config.get("MXNET_KV_POOL_PAGES"))
        if page_size < 1 or num_pages < 2:
            raise MXNetError(
                f"KV pool needs page_size >= 1 and num_pages >= 2 (one "
                f"scratch + one usable), got page_size={page_size}, "
                f"num_pages={num_pages}")
        self.name = name
        self.num_layers = int(num_layers)
        self.kv_dim = int(kv_dim)
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.max_seq_len = int(max_seq_len)
        self.pages_per_seq = int(math.ceil(self.max_seq_len / self.page_size))
        if self.pages_per_seq > self.num_pages - 1:
            raise MXNetError(
                f"KV pool {name!r}: one sequence needs {self.pages_per_seq} "
                f"pages for max_seq_len={max_seq_len} but the pool only has "
                f"{self.num_pages - 1} usable pages")
        self.latent = bool(latent)
        # a latent's row on whole lane tiles (module docstring)
        self.row_dim = -(-self.kv_dim // _LANES) * _LANES if self.latent \
            else self.kv_dim
        shape = (self.num_layers, self.num_pages, self.page_size,
                 self.row_dim)
        # allocated on ``device`` (None: JAX's default), never staged
        # through another one — the pool is the largest array decode holds
        # the pool's arrays, as the executables take and return them: K and
        # V, or the one latent array
        with jax.default_device(device):
            self.arrays = tuple(jnp.zeros(shape, dtype=dtype)
                                for _ in range(1 if self.latent else 2))
        # bytes one cached position is, over all layers and arrays (its
        # ``kv_dim`` numbers, not a latent row's padding): what a step's
        # attention must read of each position it attends to
        self.row_bytes = sum(self.num_layers * self.kv_dim * a.dtype.itemsize
                             for a in self.arrays)
        self._lock = threading.Lock()
        # LIFO free list, page 0 (scratch) excluded for the pool's lifetime
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))
        self._tables: Dict[int, List[int]] = {}
        self._m_pages = _POOL_PAGES.labels(name)
        self._m_in_use = _IN_USE.labels(name)
        self._m_alloc = _ALLOCATED.labels(name)
        self._m_freed = _FREED.labels(name)
        self._m_exhausted = _EXHAUSTED.labels(name)
        self._m_defrags = _DEFRAGS.labels(name)
        self._m_moved = _DEFRAG_MOVED.labels(name)
        self._m_pages.set(self.num_pages - 1)
        self._m_in_use.set(0)
        from ...telemetry import memstats as _memstats
        _memstats.register(
            "serving", f"{name}.kv_pool", owner=self,
            device=self._device_label(),
            sizer=lambda p: p.nbytes)

    @property
    def k_pool(self):
        """The keys' array; a latent pool's only one."""
        return self.arrays[0]

    @property
    def v_pool(self):
        """The values' array; None of a latent pool, whose row is both."""
        return None if self.latent else self.arrays[1]

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in self.arrays)

    def _device_label(self) -> str:
        try:
            d = next(iter(self.k_pool.devices()))
            return f"{d.platform}:{d.id}"
        except Exception:
            return ""

    # -- allocation ---------------------------------------------------------
    def reserve(self, sid: int, total_tokens: int):
        """Grow ``sid``'s page table to cover ``total_tokens`` positions.

        The decode scheduler reserves a sequence's WHOLE budget
        (prompt + max_new_tokens) at admission, so exhaustion can only
        happen here — never mid-decode — and a refused sequence simply
        stays queued with nothing to unwind. Raises
        :class:`KVPoolExhausted` when the free list is short (including the
        injected ``kv_exhausted`` fault, which simulates exactly that)."""
        if total_tokens > self.max_seq_len:
            raise MXNetError(
                f"sequence {sid} wants {total_tokens} tokens, pool "
                f"{self.name!r} is laid out for max_seq_len="
                f"{self.max_seq_len}")
        need = int(math.ceil(total_tokens / self.page_size))
        try:
            _faults.check("decode")
        except _faults.FaultInjected as e:
            if e.kind == "kv_exhausted":
                self._m_exhausted.inc()
                raise KVPoolExhausted(str(e))
            raise
        with self._lock:
            table = self._tables.setdefault(sid, [])
            delta = need - len(table)
            if delta <= 0:
                return
            if delta > len(self._free):
                self._m_exhausted.inc()
                raise KVPoolExhausted(
                    f"RESOURCE_EXHAUSTED: KV pool {self.name!r} has "
                    f"{len(self._free)} free pages, sequence {sid} needs "
                    f"{delta} more (of {need} for {total_tokens} tokens)")
            for _ in range(delta):
                table.append(self._free.pop())
            in_use = (self.num_pages - 1) - len(self._free)
        self._m_alloc.inc(delta)
        self._m_in_use.set(in_use)

    def free(self, sid: int) -> int:
        """Return ``sid``'s pages to the free list; pages are reused by later
        reservations (the free -> realloc path the oracle test covers)."""
        with self._lock:
            table = self._tables.pop(sid, None)
            if not table:
                return 0
            self._free.extend(reversed(table))
            n = len(table)
            in_use = (self.num_pages - 1) - len(self._free)
        self._m_freed.inc(n)
        self._m_in_use.set(in_use)
        ratio = float(_config.get("MXNET_KV_DEFRAG_RATIO"))
        if ratio > 0 and self.spread() > ratio:
            self.defrag()
        return n

    def table(self, sid: int) -> onp.ndarray:
        """``sid``'s page table padded with scratch-page zeros to the fixed
        (pages_per_seq,) executable shape."""
        out = onp.zeros((self.pages_per_seq,), onp.int32)
        with self._lock:
            pages = self._tables.get(sid, ())
            out[:len(pages)] = pages
        return out

    # -- accounting ---------------------------------------------------------
    @property
    def pages_in_use(self) -> int:
        with self._lock:
            return (self.num_pages - 1) - len(self._free)

    def occupancy(self) -> float:
        """Fraction of usable pages owned by live sequences (0..1)."""
        return self.pages_in_use / max(1, self.num_pages - 1)

    def spread(self) -> float:
        """Fragmentation proxy: highest allocated page id / pages in use.
        1.0 means perfectly compact; large values mean live pages are
        scattered across a mostly-empty pool."""
        with self._lock:
            used = [p for t in self._tables.values() for p in t]
            if not used:
                return 1.0
            return max(used) / len(used)

    def snapshot(self) -> Dict:
        with self._lock:
            used = (self.num_pages - 1) - len(self._free)
            return {
                "pool": self.name,
                "pages": self.num_pages - 1,
                "page_size": self.page_size,
                "in_use": used,
                "occupancy": used / max(1, self.num_pages - 1),
                "sequences": len(self._tables),
                "pages_per_seq": self.pages_per_seq,
                "bytes": self.nbytes,
            }

    # -- engine hooks -------------------------------------------------------
    def update_arrays(self, *arrays):
        """Install the pool arrays a compiled step returned (worker thread
        only — the single-dispatcher rule, so no lock: defrag() and this
        never run concurrently)."""
        self.arrays = tuple(arrays)    # mxlint: disable=CONC200

    def defrag(self) -> int:
        """Compact live pages down to the lowest physical ids.

        Page-granular allocation never *functionally* fragments (any free
        page serves any reservation), so this is an optional compaction that
        keeps the high-numbered region of the pool untouched — the tail
        could be released to a resize. The move is
        a single gather+scatter copy (no arithmetic), so decode output
        stays bitwise identical across a compaction. Worker-thread only.
        Returns the number of pages moved."""
        import jax.numpy as jnp
        with self._lock:
            order = sorted(
                (p, sid, i)
                for sid, t in self._tables.items() for i, p in enumerate(t))
            moves = [(old, new + 1, sid, i)
                     for new, (old, sid, i) in enumerate(order)
                     if old != new + 1]
            if moves:
                old_ids = jnp.asarray([m[0] for m in moves], jnp.int32)
                new_ids = jnp.asarray([m[1] for m in moves], jnp.int32)
                self.arrays = tuple(a.at[:, new_ids].set(a[:, old_ids])
                                    for a in self.arrays)
                for old, new, sid, i in moves:
                    self._tables[sid][i] = new
            n_used = len(order)
            self._free = list(range(self.num_pages - 1, n_used, -1))
        self._m_defrags.inc()
        self._m_moved.inc(len(moves))
        return len(moves)
