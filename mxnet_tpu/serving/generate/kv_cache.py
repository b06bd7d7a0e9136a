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
**Cache groups.** A model whose layers do not all keep the same positions
(three layers of four that see only the last 1,024 positions of a sequence,
the fourth all of them) states its groups, and the pool holds one array set,
one free list and one page table a sequence **a group**: ``(group's layers,
group's pages, page_size, kv_dim)``. A group keeps either every position of a
sequence (``ceil(max_seq_len / page_size)`` pages a sequence, as above) or a
**window**: then a sequence's table is a *ring* of ``ceil((window +
page_size) / page_size)`` pages, whatever its length, which the writes and
the kernel index by ``(position // page_size) mod ring``: the page of
position p overwrites the page of position ``p - ring x page_size``, which
lies wholly behind ``p - window`` (the ring holds the window and one page of
slack, so the row being written never shares a page with a row still seen).
The attention kernel is given each lane's lower bound beside its length, so
what a ring's pages hold behind the bound, a former owner's rows included,
weighs exactly nothing, as what lies past the length does. One allocator
serves all groups: ``reserve`` covers the whole budget in every group or
takes nothing, ``free``, ``defrag``, ``snapshot`` and the gauges cover every
group, and the arrays ride in ``arrays`` a group after the other. One group of
all layers that keeps everything is the pool as it always was, array for
array.
**Page 0 is reserved as a scratch page** (each group's own) and never allocated:
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

__all__ = ["PagedKVPool", "KVPoolExhausted", "write_prefill", "write_step",
           "ring_pages"]

_LANES = 128        # a latent pool's row is whole tiles of this many columns

_POOL_PAGES = _telemetry.gauge(
    "mxtpu_kv_pool_pages",
    "Usable pages preallocated in one paged KV pool (page 0, the scratch "
    "page for masked writes, is excluded).",
    labelnames=("pool",))   # a pool of several groups: "<pool>.<group>"
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
_RING_OVERWRITTEN = _telemetry.counter(
    "mxtpu_kv_ring_pages_overwritten_total",
    "Pages of a window group's rings that a decode step opened over the "
    "oldest page of the same ring (positions past the ring's length).",
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
def write_prefill(pool, vals, table_row, length, page_size: int,
                  window: Optional[int] = None):
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
    multiple of ``page_size``, nor reach it.

    ``window``: the pool is a window group's and ``table_row`` a ring. Only
    the pages that hold the prompt's last ``window`` positions are written
    (what the first decode step and every later one can still see), logical
    page j into entry ``j mod P``."""
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
        start = (0, table_row[j if window is None
                              else j % table_row.shape[0]], 0, 0)
        keep = j * page_size + lane < length

        def put(p, v):
            new = lax.dynamic_slice_in_dim(v, j * page_size, page_size, 1)
            old = lax.dynamic_slice(
                p, start, (p.shape[0], 1, page_size, p.shape[3]))
            return lax.dynamic_update_slice(
                p, jnp.where(keep, new[:, None], old), start)

        return jax.tree.map(put, pools, vals)

    first = 0 if window is None else \
        jnp.maximum(length - window + 1, 0) // page_size
    return lax.fori_loop(first, -(-length // page_size), body, pool)


def write_step(pool, vals, tables, positions, valid, page_size: int,
               ring: bool = False):
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
    a denoising step's rows go to the scratch page too. ``ring``: the
    tables are a window group's, a position's page in entry ``(position //
    page_size) mod P``.

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
    if ring:
        page = tables[jnp.arange(B), first // page_size % tables.shape[1]]
    else:
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
def ring_pages(window: int, page_size: int) -> int:
    """Pages a window group keeps a sequence: ``window`` positions and one
    page of slack, so that any ``window`` consecutive positions, the row
    being written among them, lie in distinct entries of the ring."""
    return -(-(int(window) + page_size) // page_size)


class _Group:
    """One cache group's share of a pool: its layers' arrays, its free list
    and its sequences' page tables (module docstring)."""

    def __init__(self, pool_name, name, layers, window, num_pages,
                 pages_per_seq):
        self.name = name
        self.layers = int(layers)
        self.window = window
        self.num_pages = int(num_pages)
        self.pages_per_seq = int(pages_per_seq)
        self.ring = window is not None
        self.arrays = ()
        # LIFO free list, page 0 (scratch) excluded for the pool's lifetime
        self.free: List[int] = list(range(self.num_pages - 1, 0, -1))
        self.tables: Dict[int, List[int]] = {}
        self.peak_seq_pages = 0     # most pages one sequence ever held
        label = pool_name if name is None else f"{pool_name}.{name}"
        self.m_pages = _POOL_PAGES.labels(label)
        self.m_in_use = _IN_USE.labels(label)
        self.m_alloc = _ALLOCATED.labels(label)
        self.m_freed = _FREED.labels(label)
        self.m_exhausted = _EXHAUSTED.labels(label)
        self.m_overwritten = _RING_OVERWRITTEN.labels(label)
        self.m_pages.set(self.num_pages - 1)
        self.m_in_use.set(0)

    @property
    def in_use(self) -> int:
        return (self.num_pages - 1) - len(self.free)

    def pages_for(self, tokens: int, page_size: int) -> int:
        """Pages a sequence of ``tokens`` positions holds here: all of them,
        or no more than the ring."""
        return min(int(math.ceil(tokens / page_size)), self.pages_per_seq)


class PagedKVPool:
    """Preallocated paged KV storage plus its free-list allocator.

    ``groups`` (default: one group of all ``num_layers`` layers that keeps
    every position) is a sequence of ``(name, layers, window)``: how many of
    the model's layers a group holds and the positions it keeps a sequence,
    None for all of them (module docstring). ``num_pages`` is a whole
    group's; a window group holds ``max_seqs`` rings and its scratch page
    (without ``max_seqs``, as many rings as whole sequences fit the whole
    group's pages).

    Thread-safety: all mutators take the internal lock, but array
    replacement (``update_arrays``) and ``defrag`` follow the serving
    single-dispatcher rule — only the decode worker thread runs them, so a
    step never races a compaction.
    """

    def __init__(self, name: str, num_layers: int, kv_dim: int,
                 max_seq_len: int, page_size: Optional[int] = None,
                 num_pages: Optional[int] = None, dtype="float32",
                 device=None, latent: bool = False, groups=None,
                 max_seqs: Optional[int] = None):
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
        whole = int(math.ceil(self.max_seq_len / self.page_size))
        if whole > self.num_pages - 1:
            raise MXNetError(
                f"KV pool {name!r}: one sequence needs {whole} "
                f"pages for max_seq_len={max_seq_len} but the pool only has "
                f"{self.num_pages - 1} usable pages")
        self.latent = bool(latent)
        # a latent's row on whole lane tiles (module docstring)
        self.row_dim = -(-self.kv_dim // _LANES) * _LANES if self.latent \
            else self.kv_dim
        if groups is None:
            groups = ((None, self.num_layers, None),)
        if sum(int(g[1]) for g in groups) != self.num_layers:
            raise MXNetError(
                f"KV pool {name!r}: the groups' layers {groups!r} are not "
                f"the model's {self.num_layers}")
        self.groups: List[_Group] = []
        for gname, layers, window in groups:
            if window is None:
                pages, per_seq = self.num_pages, whole
            else:
                per_seq = min(ring_pages(window, self.page_size), whole)
                seqs = max_seqs if max_seqs is not None \
                    else (self.num_pages - 1) // whole
                pages = min(int(seqs) * per_seq, self.num_pages - 1) + 1
            self.groups.append(_Group(name, gname, layers, window, pages,
                                      per_seq))
        # a sequence's table row as the executables take it: the groups'
        # rows side by side
        self.pages_per_seq = sum(g.pages_per_seq for g in self.groups)
        # allocated on ``device`` (None: JAX's default), never staged
        # through another one — the pool is the largest array decode holds
        # the pool's arrays, as the executables take and return them: K and
        # V, or the one latent array, a group after the other
        self._arrays_a_group = 1 if self.latent else 2
        with jax.default_device(device):
            arrays = tuple(
                jnp.zeros((g.layers, g.num_pages, self.page_size,
                           self.row_dim), dtype=dtype)
                for g in self.groups for _ in range(self._arrays_a_group))
        self.update_arrays(*arrays)
        # bytes one cached position is, over all layers and arrays (its
        # ``kv_dim`` numbers, not a latent row's padding): what a step's
        # attention must read of each position it attends to, were every
        # layer to keep it (a group's own: ``group_row_bytes``)
        itemsize = arrays[0].dtype.itemsize
        self.group_row_bytes = tuple(
            self._arrays_a_group * g.layers * self.kv_dim * itemsize
            for g in self.groups)
        self.row_bytes = sum(self.group_row_bytes)
        self._lock = threading.Lock()
        self._m_defrags = _DEFRAGS.labels(name)
        self._m_moved = _DEFRAG_MOVED.labels(name)
        from ...telemetry import memstats as _memstats
        _memstats.register(
            "serving", f"{name}.kv_pool", owner=self,
            device=self._device_label(),
            sizer=lambda p: p.nbytes)

    @property
    def k_pool(self):
        """The keys' array (the first group's); a latent pool's only one."""
        return self.arrays[0]

    @property
    def v_pool(self):
        """The values' array; None of a latent pool, whose row is both."""
        return None if self.latent else self.arrays[1]

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in self.arrays)

    @property
    def _tables(self):
        """The first group's page tables by sequence."""
        return self.groups[0].tables

    def _device_label(self) -> str:
        try:
            d = next(iter(self.k_pool.devices()))
            return f"{d.platform}:{d.id}"
        except Exception:
            return ""

    # -- allocation ---------------------------------------------------------
    def reserve(self, sid: int, total_tokens: int):
        """Grow ``sid``'s page tables to cover ``total_tokens`` positions in
        every group: all of them in a group that keeps everything, the ring
        in a window group. All or nothing: a group that is short refuses
        the whole reservation and nothing is taken from the others.

        The decode scheduler reserves a sequence's WHOLE budget
        (prompt + max_new_tokens) at admission, so exhaustion can only
        happen here — never mid-decode — and a refused sequence simply
        stays queued with nothing to unwind. Raises
        :class:`KVPoolExhausted` when a free list is short (including the
        injected ``kv_exhausted`` fault, which simulates exactly that)."""
        if total_tokens > self.max_seq_len:
            raise MXNetError(
                f"sequence {sid} wants {total_tokens} tokens, pool "
                f"{self.name!r} is laid out for max_seq_len="
                f"{self.max_seq_len}")
        try:
            _faults.check("decode")
        except _faults.FaultInjected as e:
            if e.kind == "kv_exhausted":
                self.groups[0].m_exhausted.inc()
                raise KVPoolExhausted(str(e))
            raise
        with self._lock:
            deltas = []
            for g in self.groups:
                need = g.pages_for(total_tokens, self.page_size)
                delta = need - len(g.tables.get(sid, ()))
                if delta > len(g.free):
                    g.m_exhausted.inc()
                    raise KVPoolExhausted(
                        f"RESOURCE_EXHAUSTED: KV pool {self.name!r}"
                        f"{'' if g.name is None else ' group ' + g.name} has "
                        f"{len(g.free)} free pages, sequence {sid} needs "
                        f"{delta} more (of {need} for {total_tokens} tokens)")
                deltas.append(max(delta, 0))
            for g, delta in zip(self.groups, deltas):
                table = g.tables.setdefault(sid, [])
                for _ in range(delta):
                    table.append(g.free.pop())
                g.peak_seq_pages = max(g.peak_seq_pages, len(table))
            in_use = [g.in_use for g in self.groups]
        for g, delta, used in zip(self.groups, deltas, in_use):
            if delta:
                g.m_alloc.inc(delta)
                g.m_in_use.set(used)

    def free(self, sid: int) -> int:
        """Return ``sid``'s pages, every group's, to the free lists; pages are
        reused by later reservations (the free -> realloc path the oracle
        test covers). What a freed ring still holds lies behind the next
        owner's bound or past its length, as a freed page's rows do."""
        n = 0
        with self._lock:
            freed = []
            for g in self.groups:
                table = g.tables.pop(sid, None) or []
                g.free.extend(reversed(table))
                freed.append((g, len(table), g.in_use))
                n += len(table)
        if not n:
            return 0
        for g, count, used in freed:
            g.m_freed.inc(count)
            g.m_in_use.set(used)
        ratio = float(_config.get("MXNET_KV_DEFRAG_RATIO"))
        if ratio > 0 and self.spread() > ratio:
            self.defrag()
        return n

    def table(self, sid: int) -> onp.ndarray:
        """``sid``'s page table padded with scratch-page zeros to the fixed
        (pages_per_seq,) executable shape: the groups' rows side by side
        (:meth:`split_tables` takes a batch of them apart again)."""
        out = onp.zeros((self.pages_per_seq,), onp.int32)
        at = 0
        with self._lock:
            for g in self.groups:
                pages = g.tables.get(sid, ())
                out[at:at + len(pages)] = pages
                at += g.pages_per_seq
        return out

    def split_tables(self, tables: onp.ndarray):
        """(B, pages_per_seq) rows of :meth:`table` as an executable takes
        them: the one array of a pool of one group, else a tuple of each
        group's (B, its pages a sequence)."""
        if len(self.groups) == 1:
            return tables
        edges = onp.cumsum([g.pages_per_seq for g in self.groups])[:-1]
        return tuple(onp.ascontiguousarray(t)
                     for t in onp.split(tables, edges, axis=1))

    def group_arrays(self, i: int):
        """Group ``i``'s arrays (K and V, or its latent) of ``arrays``."""
        n = self._arrays_a_group
        return self.arrays[i * n:(i + 1) * n]

    def ring_overwrites(self, positions) -> int:
        """Count, of the positions a step is about to write, those that open
        a ring page over the oldest one (a window group's counter of pages
        overwritten); returns how many."""
        n = 0
        opened = [p // self.page_size for p in positions
                  if p % self.page_size == 0]
        for g in self.groups:
            if g.ring:
                k = sum(page >= g.pages_per_seq for page in opened)
                if k:
                    g.m_overwritten.inc(k)
                n += k
        return n

    # -- accounting ---------------------------------------------------------
    @property
    def pages_in_use(self) -> int:
        with self._lock:
            return sum(g.in_use for g in self.groups)

    def occupancy(self) -> float:
        """Fraction of usable pages owned by live sequences (0..1)."""
        return self.pages_in_use / max(
            1, sum(g.num_pages - 1 for g in self.groups))

    def spread(self) -> float:
        """Fragmentation proxy: highest allocated page id / pages in use,
        the most fragmented group's. 1.0 means perfectly compact; large
        values mean live pages are scattered across a mostly-empty pool."""
        with self._lock:
            worst = 1.0
            for g in self.groups:
                used = [p for t in g.tables.values() for p in t]
                if used:
                    worst = max(worst, max(used) / len(used))
            return worst

    def snapshot(self) -> Dict:
        with self._lock:
            pages = sum(g.num_pages - 1 for g in self.groups)
            used = sum(g.in_use for g in self.groups)
            out = {
                "pool": self.name,
                "pages": pages,
                "page_size": self.page_size,
                "in_use": used,
                "occupancy": used / max(1, pages),
                "sequences": len(self.groups[0].tables),
                "pages_per_seq": self.pages_per_seq,
                "bytes": self.nbytes,
            }
            if len(self.groups) > 1:
                out["groups"] = [{
                    "group": g.name, "layers": g.layers, "window": g.window,
                    "pages": g.num_pages - 1, "in_use": g.in_use,
                    "pages_per_seq": g.pages_per_seq,
                    "peak_seq_pages": g.peak_seq_pages,
                    "bytes": sum(int(a.nbytes) for a in g.arrays)}
                    for g in self.groups]
            return out

    # -- engine hooks -------------------------------------------------------
    def update_arrays(self, *arrays):
        """Install the pool arrays a compiled step returned (worker thread
        only — the single-dispatcher rule, so no lock: defrag() and this
        never run concurrently)."""
        self.arrays = tuple(arrays)    # mxlint: disable=CONC200
        for i, g in enumerate(self.groups):
            g.arrays = self.group_arrays(i)

    def defrag(self) -> int:
        """Compact live pages down to the lowest physical ids, in every
        group.

        Page-granular allocation never *functionally* fragments (any free
        page serves any reservation), so this is an optional compaction that
        keeps the high-numbered region of the pool untouched — the tail
        could be released to a resize. The move is
        a single gather+scatter copy (no arithmetic), so decode output
        stays bitwise identical across a compaction. Worker-thread only.
        Returns the number of pages moved."""
        import jax.numpy as jnp
        moved = 0
        with self._lock:
            arrays = []
            for g in self.groups:
                order = sorted(
                    (p, sid, i)
                    for sid, t in g.tables.items() for i, p in enumerate(t))
                moves = [(old, new + 1, sid, i)
                         for new, (old, sid, i) in enumerate(order)
                         if old != new + 1]
                if moves:
                    old_ids = jnp.asarray([m[0] for m in moves], jnp.int32)
                    new_ids = jnp.asarray([m[1] for m in moves], jnp.int32)
                    g.arrays = tuple(a.at[:, new_ids].set(a[:, old_ids])
                                     for a in g.arrays)
                    for old, new, sid, i in moves:
                        g.tables[sid][i] = new
                g.free = list(range(g.num_pages - 1, len(order), -1))
                arrays += g.arrays
                moved += len(moves)
            self.arrays = tuple(arrays)
        self._m_defrags.inc()
        self._m_moved.inc(moved)
        return moved
