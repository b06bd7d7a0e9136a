"""Mesh-sharded endpoint twins: one logical replica spanning N chips.

:class:`ShardedEndpoint` and :class:`ShardedDecodeEndpoint` are drop-in
subclasses of ``serving.ModelEndpoint`` / ``serving.generate.DecodeEndpoint``
whose bucket executables compile with ``NamedSharding`` in/out shardings
over a gang-scheduled slice's mesh (:mod:`.slices`). Everything else —
the AOT compile path through ``compile_ledger.lower_and_compile``, the
per-bucket executable dict, warmup seeding StepCostEWMA, the persistent
executable cache, hot-swap probe validation — is inherited unchanged: the
sharding enters only through four small hooks (jit wrapping, input/param
placement, and the cache trigger key).

Bitwise contract (the tier-1 oracle): a sharded replica's outputs equal the
single-chip reference endpoint's bit for bit. Two rules make that true by
construction rather than by luck:

- only the **batch (row) axis** of inputs and outputs is ever sharded.
  Every per-row computation then happens whole on one device — no
  contraction dimension is ever split, so no floating-point reduction is
  reordered;
- parameters shard along their **leading axis** where divisible (fsdp-style
  memory spreading) and replicate otherwise. Consuming a leading-axis
  shard is an all-gather — a byte move, not arithmetic.

Uneven sharding is a compile error in XLA (a global batch axis must divide
by the mesh axis), so a sharded endpoint's bucket ladder may only contain
multiples of its slice's batch-axis size; the default ladder is the pow2
ladder filtered down to those.

Cache-key topology rule: the trigger key must carry the slice *shape*
(axis sizes), never concrete device ids — the canonical StableHLO of a
sharded lowering is identical for any equal-shaped slice, so a restarted
replica that lands on different chips of the same shape deserializes the
fleet's stored executables (``fresh_compiles == 0``).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as onp

from ...base import MXNetError
from ...parallel.mesh import DeviceMesh
from .. import bucketing
from ..endpoint import ModelEndpoint
from ..generate.engine import DecodeEndpoint
from .slices import SliceSpec

__all__ = ["ShardedEndpoint", "ShardedDecodeEndpoint"]


def _compiled_mesh(comp):
    """The jax Mesh an executable's inputs are bound to, or None.

    A cache-deserialized executable is bound to the device assignment
    recorded at serialize time — the same slice *shape*, but possibly
    different chips than this replica nominally carved. The endpoint
    adopts that mesh so its placements match (fingerprint and trigger key
    are topology-stable, so every bucket of one endpoint deserializes onto
    the same assignment)."""
    import jax
    try:
        shardings = comp.input_shardings
    except Exception:
        return None
    for sh in jax.tree_util.tree_leaves(shardings):
        m = getattr(sh, "mesh", None)
        if m is not None and getattr(m, "devices", None) is not None:
            return m
    return None


def _resolve_mesh(slice_spec: Optional[SliceSpec],
                  mesh: Optional[DeviceMesh]) -> DeviceMesh:
    if slice_spec is not None:
        if mesh is not None:
            raise MXNetError("pass slice_spec OR mesh, not both")
        return slice_spec.make_mesh()
    if mesh is None:
        raise MXNetError("a sharded endpoint needs a slice_spec or mesh")
    return mesh


def _mesh_label(mesh: DeviceMesh) -> str:
    """Topology-stable slice label: axis layout, not device ids."""
    return ",".join(f"{a}={s}" for a, s in sorted(mesh.shape.items()))


def _sharded_buckets(buckets: Optional[Sequence[int]], max_batch_size: int,
                     shard: int) -> Sequence[int]:
    """Bucket ladder constrained to multiples of the batch-shard size:
    XLA rejects a global batch axis the mesh axis does not divide."""
    if max_batch_size % shard:
        raise MXNetError(
            f"max_batch_size={max_batch_size} must be a multiple of the "
            f"slice's batch-shard size {shard} (uneven batch sharding "
            "does not compile)")
    if buckets is None:
        return [b for b in bucketing.pow2_buckets(max_batch_size)
                if b % shard == 0]
    bad = [b for b in buckets if int(b) % shard]
    if bad:
        raise MXNetError(
            f"buckets {bad} are not multiples of the batch-shard size "
            f"{shard}; every sharded bucket's batch axis must divide by it")
    return buckets


class ShardedEndpoint(ModelEndpoint):
    """A ModelEndpoint whose replica spans every chip of one mesh slice.

    Parameters beyond ModelEndpoint's:

    slice_spec : SliceSpec, optional
        The gang-scheduled slice (from :func:`.slices.plan_slices`) this
        replica owns. ``capacity`` becomes its device count.
    mesh : DeviceMesh, optional
        Explicit mesh alternative to ``slice_spec``.
    shard_params : bool
        Shard each parameter along its leading axis over the batch axis
        where the size divides (fsdp-style: per-chip weight memory drops by
        ~the slice size); non-divisible parameters replicate. All-gather
        only — bitwise-invisible. Default True.
    """

    def __init__(self, name: str, block, input_shapes, dtype="float32",
                 max_batch_size: int = 32,
                 buckets: Optional[Sequence[int]] = None,
                 slice_spec: Optional[SliceSpec] = None,
                 mesh: Optional[DeviceMesh] = None,
                 shard_params: bool = True, ctx=None):
        dmesh = _resolve_mesh(slice_spec, mesh)
        self.slice_spec = slice_spec
        self._dmesh = dmesh
        self._batch_axis = dmesh.axis_names[0]
        self._shard = dmesh.axis_size(self._batch_axis)
        self._shard_params = bool(shard_params)
        self.capacity = dmesh.size
        self._placed_params = None
        self._placed_key = None
        buckets = _sharded_buckets(buckets, int(max_batch_size), self._shard)
        super().__init__(name, block, input_shapes, dtype=dtype,
                         max_batch_size=max_batch_size, buckets=buckets,
                         ctx=ctx)

    # -- sharding layout ------------------------------------------------
    def _batch_sharding(self):
        return self._dmesh.sharding(self._batch_axis)

    def _param_shardings(self):
        repl = self._dmesh.replicated()
        if not self._shard_params:
            return tuple(repl for _ in self._params)
        rowsh = self._batch_sharding()
        return tuple(
            rowsh if (len(p.shape) >= 1 and p.shape[0] % self._shard == 0)
            else repl
            for p in self._params)

    def _platform(self) -> str:
        return self._dmesh.mesh.devices.flat[0].platform

    def _device_label(self) -> str:
        return f"{self._platform()}:{_mesh_label(self._dmesh)}"

    def _compile_key(self, bucket: int) -> Dict[str, object]:
        # the mesh label rides into the compile ledger and the executable
        # cache's trigger key: one slice topology, one set of executables
        key = super()._compile_key(bucket)
        key["mesh"] = _mesh_label(self._dmesh)
        return key

    def _adopt_compiled(self, comp):
        m = _compiled_mesh(comp)
        if m is None:
            return
        if set(m.devices.flat) != set(self._dmesh.mesh.devices.flat):
            self._dmesh = DeviceMesh(m)
            self._placed_params = None     # re-place onto the adopted mesh
            self._placed_key = None

    def prepare(self, host_inputs, rows: int, parity: int = 0):
        # adoption must precede placement: materialize the bucket's
        # executable first (idempotent, lock-protected) so an unwarmed
        # endpoint's first batch still places onto the bound mesh
        self._get_executable(bucketing.bucket_for(rows, self.buckets))
        return super().prepare(host_inputs, rows, parity=parity)

    # -- the four sharding hooks ----------------------------------------
    def _jit_infer(self, infer, donate):
        import jax
        bsh = self._batch_sharding()
        in_sh = (self._param_shardings(),) + \
            (bsh,) * len(self.input_shapes)
        # out_shardings as a prefix: every (batch-major) output row-shards
        return jax.jit(infer, donate_argnums=donate,
                       in_shardings=in_sh, out_shardings=bsh)

    def _place_inputs(self, arrays):
        import jax
        bsh = self._batch_sharding()
        return tuple(jax.device_put(onp.asarray(a), bsh) for a in arrays)

    def _place_params(self, arrays):
        import jax
        return tuple(jax.device_put(a, sh)
                     for a, sh in zip(arrays, self._param_shardings()))

    def _param_datas(self):
        if self._active_params is not None:     # hot-swap committed set,
            return self._active_params          # already mesh-placed
        base = tuple(p.data(self.ctx).data for p in self._params)
        key = tuple(id(a) for a in base)
        if key != self._placed_key:
            self._placed_params = self._place_params(base)
            self._placed_key = key
        return self._placed_params

    def _warmup_inputs(self, bucket: int):
        # plain numpy: an uncommitted host array auto-places per the
        # compiled sharding (a committed single-device array would not)
        return tuple(onp.zeros((bucket,) + s, dt)
                     for s, dt in zip(self.input_shapes, self.np_dtypes))

    def __repr__(self):
        return (f"ShardedEndpoint({self.name!r}, "
                f"mesh={_mesh_label(self._dmesh)}, "
                f"inputs={self.input_shapes}, buckets={self.buckets})")


class ShardedDecodeEndpoint(DecodeEndpoint):
    """A DecodeEndpoint twin over a mesh slice.

    Layout: the decode-step batch row-shards over the slice's batch axis
    (its bucket ladder is constrained to multiples of the shard size, like
    the dense twin); prefill (batch 1) and the paged KV pools replicate —
    replication across N chips is trivially bitwise, and the pool write
    then moves bytes only. Parameters replicate (a generative
    model's embedding/vocab tables are the likeliest leading-axis
    mismatches, so the dense twin's fsdp-style spreading is not defaulted
    here).
    """

    def __init__(self, name: str, block, *,
                 slice_spec: Optional[SliceSpec] = None,
                 mesh: Optional[DeviceMesh] = None,
                 max_batch_size: Optional[int] = None,
                 decode_buckets: Optional[Sequence[int]] = None, **kw):
        dmesh = _resolve_mesh(slice_spec, mesh)
        self.slice_spec = slice_spec
        self._dmesh = dmesh
        self._batch_axis = dmesh.axis_names[0]
        self._shard = dmesh.axis_size(self._batch_axis)
        self.capacity = dmesh.size
        self._placed_params = None
        self._placed_key = None
        if max_batch_size is None:
            from ... import config as _config
            max_batch_size = int(_config.get("MXNET_DECODE_MAX_BATCH"))
        decode_buckets = _sharded_buckets(decode_buckets,
                                          int(max_batch_size), self._shard)
        super().__init__(name, block, max_batch_size=max_batch_size,
                         decode_buckets=decode_buckets, **kw)
        import jax
        repl = self._dmesh.replicated()
        # the pools ride as executable arguments: committed single-device
        # arrays are rejected by a sharded AOT call, so place them
        # replicated once; every later update keeps the mesh placement
        self.pool.update_arrays(*(jax.device_put(a, repl)
                                  for a in self.pool.arrays))

    def _platform(self) -> str:
        return self._dmesh.mesh.devices.flat[0].platform

    def _device_label(self) -> str:
        return f"{self._platform()}:{_mesh_label(self._dmesh)}"

    def _cost_key(self, kind: str, bucket: int) -> Dict[str, object]:
        # mirror the dense twin: slice topology reaches the ledger and
        # the executable cache's trigger key
        key = super()._cost_key(kind, bucket)
        key["mesh"] = _mesh_label(self._dmesh)
        return key

    def _adopt_compiled(self, comp):
        m = _compiled_mesh(comp)
        if m is None:
            return
        if set(m.devices.flat) != set(self._dmesh.mesh.devices.flat):
            import jax
            self._dmesh = DeviceMesh(m)
            self._placed_params = None
            self._placed_key = None
            repl = self._dmesh.replicated()
            self.pool.update_arrays(*(
                jax.device_put(onp.asarray(a), repl)
                for a in self.pool.arrays))

    def _param_datas(self):
        import jax
        base = super()._param_datas()
        key = tuple(id(a) for a in base)
        if key != self._placed_key:
            repl = self._dmesh.replicated()
            self._placed_params = tuple(jax.device_put(a, repl)
                                        for a in base)
            self._placed_key = key
        return self._placed_params

    def _jit_prefill(self, fn, donate):
        import jax
        repl = self._dmesh.replicated()
        # batch 1 cannot shard: the whole prefill replicates (bitwise by
        # construction); params, three operands and the pool's arrays — the
        # params tree takes repl as a prefix
        return jax.jit(fn, donate_argnums=donate,
                       in_shardings=(repl,) * (4 + len(self.pool.arrays)),
                       out_shardings=repl)

    def _jit_decode(self, fn, donate):
        import jax
        repl = self._dmesh.replicated()
        bsh = self._dmesh.sharding(self._batch_axis)
        pools = (repl,) * len(self.pool.arrays)     # K and V, or a latent
        # (params, ids, positions, tables, valid, the pools)
        in_sh = (repl, bsh, bsh, bsh, bsh) + pools
        # (next_ids, the pools)
        return jax.jit(fn, donate_argnums=donate,
                       in_shardings=in_sh, out_shardings=(bsh,) + pools)

    def __repr__(self):
        return (f"ShardedDecodeEndpoint({self.name!r}, "
                f"mesh={_mesh_label(self._dmesh)}, "
                f"decode_buckets={self.decode_buckets})")
