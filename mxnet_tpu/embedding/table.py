"""Vocab-sharded embedding tables with a mesh-collective sparse path.

The reference serves large embeddings through the parameter-server sparse
path: ``row_sparse`` weights live in the KVStore, workers ``row_sparse_pull``
the rows a batch touches and push RowSparse gradients back through the host
(python/mxnet/kvstore.py PullRowSparse / src/kvstore/kvstore_dist.h). Every
lookup and every update round-trips device→host→device.

Here the table is partitioned along the **vocab axis** over a named mesh axis
(parallel/mesh.py) and both directions stay on the mesh, inside the compiled
step, as XLA collectives (parallel/collectives.py):

  lookup   dedup indices (the ``sparse._dedup_fn`` convention: sorted unique
           ids padded with an out-of-range sentinel) → ``all_to_all`` index
           dispatch to the owning shards → local gather → ``all_to_all``
           result return. GSPMD/XLA fuses the exchange with the surrounding
           step; nothing leaves the device.
  update   RowSparse semantics without the host: the step differentiates
           w.r.t. the *gathered rows* (never materializing a dense (V, D)
           cotangent), routes the per-row gradients back to their owning
           shards through the reverse exchange, and applies them as a
           shard-local scatter-add.

Two lookup kernels are exposed, picked by how the index batch is sharded:

  ``gather_fn``            indices REPLICATED over the axis — each shard
                           contributes its owned rows (masked local gather)
                           and a psum assembles the result. Exactly one
                           shard contributes a given row and the others add
                           exact zeros, so the assembled rows are bitwise
                           equal to a single-device dense gather — the
                           property the tier-1 oracle pins.
  ``dispatch_gather_fn``   indices SHARDED over the axis (each shard holds
                           its own batch slice) — the all_to_all dispatch /
                           return exchange described above.

Row placement within the partition supports two layouts: ``block`` (shard s
owns the contiguous range [s*rows_per_shard, ...)) and ``cyclic`` (row r
lives on shard ``r % n_shards`` — the planner's "row-wise" placement, which
spreads a frequency-sorted vocabulary's hot head across every shard instead
of concentrating it on shard 0).
"""
from __future__ import annotations

import functools
import time
from typing import Optional, Tuple

import numpy as onp

from ..base import MXNetError
from .. import telemetry as _telemetry

__all__ = ["ShardedEmbedding", "dedup_ids"]

_LOOKUP_US = _telemetry.histogram(
    "mxtpu_emb_lookup_us",
    "Eager embedding lookup wall time (dedup + exchange + gather), "
    "microseconds.", labelnames=("table",))
_EXCHANGE_BYTES = _telemetry.counter(
    "mxtpu_emb_exchange_bytes_total",
    "Estimated bytes moved by the on-mesh embedding exchange (all_to_all "
    "index dispatch + row return, or psum assembly), by direction.",
    labelnames=("table", "direction"))


@functools.lru_cache(maxsize=None)
def _dedup_ids_fn():
    """Jitted id dedup, mirroring ``sparse._dedup_fn``'s convention: sorted
    unique int32 ids padded to the input nnz with ``vocab`` (an out-of-range
    sentinel every gather/scatter drops), plus the inverse map that rebuilds
    the original order. One shared executable, so a host-staged bundle
    (feed.py) and an in-step dedup are the same computation bit for bit."""
    import jax
    import jax.numpy as jnp

    def dedup(idx, vocab):
        flat = idx.reshape(-1).astype(jnp.int32)
        n = flat.shape[0]
        uniq, inv = jnp.unique(flat, return_inverse=True, size=n,
                               fill_value=vocab)
        return uniq.astype(jnp.int32), inv.reshape(idx.shape).astype(jnp.int32)

    return jax.jit(dedup, static_argnums=(1,))


def dedup_ids(idx, vocab: int):
    """Dedup an index batch: (sorted unique ids padded with ``vocab``,
    inverse map). Accepts any int array; returns jax arrays."""
    return _dedup_ids_fn()(idx, int(vocab))


class ShardedEmbedding:
    """One embedding table, partitioned (or replicated) over a mesh axis.

    Parameters
    ----------
    vocab_size, embed_dim : int
        Logical table shape. The stored array pads the vocab to a multiple
        of the shard count.
    mesh : parallel.DeviceMesh
        The mesh the table lives on.
    axis : str
        Mesh axis the vocab dimension is partitioned over.
    placement : str
        ``partition`` (vocab-sharded) or ``replicate`` (small tables: a full
        copy per shard, no exchange). The planner (planner.py) picks this.
    layout : str
        ``block`` or ``cyclic`` row placement (partition only; see module
        docstring). The planner's "rowwise" placement is cyclic layout.
    weight : array, optional
        Initial dense (vocab, dim) weights; default zeros.
    """

    def __init__(self, vocab_size: int, embed_dim: int, mesh, axis: str = "tp",
                 dtype: str = "float32", placement: str = "partition",
                 layout: str = "block", name: str = "emb",
                 weight=None):
        if placement not in ("partition", "replicate"):
            raise MXNetError(f"unknown placement {placement!r}")
        if layout not in ("block", "cyclic"):
            raise MXNetError(f"unknown layout {layout!r}")
        if axis not in mesh.axis_names:
            raise MXNetError(f"mesh has no axis {axis!r}: {mesh.axis_names}")
        self.name = name
        self.vocab_size = int(vocab_size)
        self.embed_dim = int(embed_dim)
        self.mesh = mesh
        self.axis = axis
        self.dtype = dtype
        self.placement = placement
        self.layout = layout
        self.n_shards = int(mesh.axis_size(axis)) if placement == "partition" \
            else 1
        self.rows_per_shard = -(-self.vocab_size // self.n_shards)
        self.padded_vocab = self.rows_per_shard * self.n_shards
        self._itemsize = onp.dtype(dtype).itemsize
        self._weight = None
        self.set_weight(weight if weight is not None else
                        onp.zeros((self.vocab_size, self.embed_dim), dtype))

    # ------------------------------------------------------------------
    # storage layout
    # ------------------------------------------------------------------
    def _stored_index(self, ids):
        """Logical row id -> row index in the stored (padded_vocab, D) array."""
        if self.layout == "block":
            return ids
        n = self.n_shards
        return (ids % n) * self.rows_per_shard + ids // n

    def sharding(self):
        if self.placement == "replicate":
            return self.mesh.replicated()
        return self.mesh.sharding(self.axis, None)

    @property
    def weight(self):
        """The live stored-layout (padded_vocab, embed_dim) device array."""
        return self._weight

    def set_weight(self, dense):
        """Install dense logical (vocab, dim) weights (host or device)."""
        import jax
        dense = onp.asarray(dense, dtype=self.dtype)
        if dense.shape != (self.vocab_size, self.embed_dim):
            raise MXNetError(
                f"weight shape {dense.shape} != "
                f"{(self.vocab_size, self.embed_dim)}")
        stored = onp.zeros((self.padded_vocab, self.embed_dim), self.dtype)
        stored[self._stored_index(onp.arange(self.vocab_size))] = dense
        self._weight = jax.device_put(stored, self.sharding())

    def set_stored(self, stored):
        """Install a stored-layout array (checkpoint restore path)."""
        import jax
        if tuple(stored.shape) != (self.padded_vocab, self.embed_dim):
            raise MXNetError(f"stored shape {tuple(stored.shape)} != "
                             f"{(self.padded_vocab, self.embed_dim)}")
        self._weight = jax.device_put(stored, self.sharding())

    def dense_weight(self) -> onp.ndarray:
        """The logical (vocab, dim) table as a host array."""
        import jax
        stored = onp.asarray(jax.device_get(self._weight))
        return stored[self._stored_index(onp.arange(self.vocab_size))]

    # ------------------------------------------------------------------
    # pure kernels (build once, close over static geometry; safe in jit)
    # ------------------------------------------------------------------
    def _owner_local(self, jnp, ids):
        """(in-kernel) ids -> (local row on this shard, ownership mask)."""
        import jax
        rps = self.rows_per_shard
        i = jax.lax.axis_index(self.axis)
        if self.layout == "block":
            local = ids - i * rps
        else:
            local = jnp.where(ids % self.n_shards == i, ids // self.n_shards,
                              rps)
        ok = (local >= 0) & (local < rps)
        return jnp.where(ok, local, rps), ok

    def gather_fn(self):
        """Pure ``(table, uniq_ids) -> (n, D) rows`` for ids REPLICATED over
        the axis: masked local gather + psum assembly (bitwise-exact rows —
        one shard contributes each row, the rest add exact zeros)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        if self.placement == "replicate":
            def gather_rep(tbl, ids):
                return tbl.at[ids].get(mode="fill", fill_value=0)
            return gather_rep

        axis = self.axis

        def _local(tbl, ids):
            local, ok = self._owner_local(jnp, ids)
            rows = jnp.where(ok[:, None],
                             tbl.at[local].get(mode="fill", fill_value=0), 0)
            return jax.lax.psum(rows, axis)

        return jax.shard_map(
            _local, mesh=self.mesh.mesh,
            in_specs=(P(axis, None), P()), out_specs=P(),
            check_vma=False)

    def dispatch_gather_fn(self):
        """Pure ``(table, local_ids) -> (n_local, D)`` for ids SHARDED over
        the axis: all_to_all index dispatch → local gather → all_to_all
        result return (the EP-style exchange; one owner contributes each
        row, the sum over owners adds exact zeros)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from ..parallel import collectives

        if self.placement == "replicate":
            def gather_rep(tbl, ids):
                return tbl.at[ids].get(mode="fill", fill_value=0)
            return gather_rep

        axis, n = self.axis, self.n_shards

        def _local(tbl, ids):
            # dispatch: every shard offers its ids to every owner
            send = jnp.broadcast_to(ids[None, :], (n, ids.shape[0]))
            recv = collectives.all_to_all(send, axis, 0, 0)
            local, ok = self._owner_local(jnp, recv.reshape(-1))
            rows = jnp.where(ok[:, None],
                             tbl.at[local].get(mode="fill", fill_value=0), 0)
            rows = rows.reshape(n, ids.shape[0], -1)
            # return: each shard gets its own ids' rows, one owner each
            back = collectives.all_to_all(rows, axis, 0, 0)
            return back.sum(0)

        return jax.shard_map(
            _local, mesh=self.mesh.mesh,
            in_specs=(P(axis, None), P(axis)), out_specs=P(axis),
            check_vma=False)

    def scatter_add_fn(self):
        """Pure ``(table, uniq_ids, updates) -> table`` for ids REPLICATED
        over the axis: shard-local scatter-add of already-deduped row
        updates (non-owned and sentinel rows drop)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        if self.placement == "replicate":
            def scat_rep(tbl, ids, upd):
                return tbl.at[ids].add(upd.astype(tbl.dtype), mode="drop")
            return scat_rep

        axis = self.axis

        def _local(tbl, ids, upd):
            local, _ = self._owner_local(jnp, ids)
            return tbl.at[local].add(upd.astype(tbl.dtype), mode="drop")

        return jax.shard_map(
            _local, mesh=self.mesh.mesh,
            in_specs=(P(axis, None), P(), P()), out_specs=P(axis, None),
            check_vma=False)

    def dispatch_scatter_add_fn(self):
        """Pure ``(table, local_ids, local_updates) -> table`` for ids
        SHARDED over the axis: the reverse exchange — route each shard's row
        gradients to the owning shards, then scatter-add locally."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from ..parallel import collectives

        if self.placement == "replicate":
            def scat_rep(tbl, ids, upd):
                return tbl.at[ids].add(upd.astype(tbl.dtype), mode="drop")
            return scat_rep

        axis, n = self.axis, self.n_shards

        def _local(tbl, ids, upd):
            send_ids = jnp.broadcast_to(ids[None, :], (n, ids.shape[0]))
            send_upd = jnp.broadcast_to(upd[None], (n,) + upd.shape)
            recv_ids = collectives.all_to_all(send_ids, axis, 0, 0)
            recv_upd = collectives.all_to_all(send_upd, axis, 0, 0)
            local, _ = self._owner_local(jnp, recv_ids.reshape(-1))
            return tbl.at[local].add(
                recv_upd.reshape(-1, upd.shape[-1]).astype(tbl.dtype),
                mode="drop")

        return jax.shard_map(
            _local, mesh=self.mesh.mesh,
            in_specs=(P(axis, None), P(axis), P(axis)),
            out_specs=P(axis, None), check_vma=False)

    # ------------------------------------------------------------------
    # telemetry
    # ------------------------------------------------------------------
    def exchange_cost_bytes(self, n_ids: int, dispatch: bool) -> Tuple[int, int]:
        """(dispatch_bytes, return_bytes) the exchange moves for ``n_ids``
        ids. Dispatch replicates the id vector to every shard; the return
        leg moves one (n_ids, D) row block per shard."""
        if self.n_shards <= 1:
            return 0, 0
        row = self.embed_dim * self._itemsize
        if dispatch:
            return (self.n_shards * n_ids * 4,
                    self.n_shards * n_ids * row)
        # psum assembly: every shard contributes an (n, D) partial
        return 0, (self.n_shards - 1) * n_ids * row

    def record_exchange(self, n_ids: int, dispatch: bool):
        d, r = self.exchange_cost_bytes(int(n_ids), dispatch)
        if d:
            _EXCHANGE_BYTES.labels(self.name, "dispatch").inc(d)
        if r:
            _EXCHANGE_BYTES.labels(self.name, "return").inc(r)

    # ------------------------------------------------------------------
    # eager convenience (serving / tests)
    # ------------------------------------------------------------------
    def lookup(self, indices):
        """Eager lookup of logical rows for (replicated) ``indices``:
        dedup → exchange/gather → re-expand. Returns a jax array shaped
        ``indices.shape + (embed_dim,)``."""
        import jax.numpy as jnp
        t0 = time.perf_counter_ns()
        idx = jnp.asarray(onp.asarray(indices), jnp.int32)
        uniq, inv = dedup_ids(idx, self.padded_vocab)
        rows = self.gather_fn()(self._weight, uniq)
        out = rows[inv]
        self.record_exchange(uniq.shape[0], dispatch=False)
        _LOOKUP_US.labels(self.name).observe(
            (time.perf_counter_ns() - t0) // 1000)
        return out

    def __repr__(self):
        return (f"ShardedEmbedding({self.name}: {self.vocab_size}x"
                f"{self.embed_dim}, {self.placement}/{self.layout} over "
                f"{self.n_shards}x'{self.axis}')")
