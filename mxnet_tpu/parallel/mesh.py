"""Named device meshes for multi-chip sharding.

The reference scales by assigning whole ops to devices (kvstore device lists,
symbol ctx_group / group2ctx at bind time, symbol.py:1562-1711). TPU-native
scaling instead names the axes of the physical device grid — dp (data), tp
(tensor), sp (sequence/context), pp (pipeline), ep (expert) — and annotates
arrays with PartitionSpecs over those axes; XLA/GSPMD inserts the collectives.

A DeviceMesh wraps jax.sharding.Mesh with axis bookkeeping and helpers to build
NamedShardings. On a v5e pod slice the mesh axes should follow the physical ICI
torus (jax's mesh_utils.create_device_mesh does this); across pod slices the
outermost axis rides DCN.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional, Sequence, Tuple

from ..base import MXNetError

__all__ = ["DeviceMesh", "make_mesh", "current_mesh", "replicated", "shard_spec",
           "carve_slices", "batch_axes", "current_batch_axes"]

_AXES = ("dp", "fsdp", "pp", "tp", "sp", "ep")  # canonical ordering, outer→inner

_current = threading.local()


class DeviceMesh:
    """A named mesh of devices (wraps jax.sharding.Mesh).

    Axis names are free-form but the canonical ones are:
      dp   data parallel (batch dim; gradients all-reduce over it)
      fsdp fully-sharded data parallel (params sharded over it, all-gathered)
      tp   tensor parallel (weight matrices sharded; activations all-reduce)
      sp   sequence/context parallel (sequence dim sharded; ring collectives)
      pp   pipeline parallel (layers sharded; ppermute between stages)
      ep   expert parallel (MoE experts sharded; all_to_all dispatch)
    """

    def __init__(self, mesh):
        self._mesh = mesh

    @property
    def mesh(self):
        return self._mesh

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(self._mesh.axis_names)

    @property
    def shape(self) -> Dict[str, int]:
        return dict(self._mesh.shape)

    @property
    def size(self) -> int:
        return self._mesh.size

    def axis_size(self, name: str) -> int:
        return self.shape.get(name, 1)

    def sharding(self, *spec):
        """NamedSharding from a PartitionSpec-style tuple; None entries replicate."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self._mesh, P(*spec))

    def replicated(self):
        return self.sharding()

    def device_positions(self, addressable_only: bool = True):
        """{device: ordinal} over the mesh's flattened device grid — the
        stable writer ids of a sharded checkpoint (shard-00003.npz is the
        shard set of mesh device #3). ``addressable_only`` keeps just this
        process's devices: each host of a multi-host job names only the
        shard files it is responsible for writing."""
        import jax
        pidx = jax.process_index()
        return {d: i for i, d in enumerate(self._mesh.devices.flat)
                if not addressable_only or d.process_index == pidx}

    def __enter__(self):
        stack = getattr(_current, "stack", None)
        if stack is None:
            stack = _current.stack = []
        stack.append(self)
        self._mesh.__enter__()
        return self

    def __exit__(self, *exc):
        _current.stack.pop()
        return self._mesh.__exit__(*exc)

    def __repr__(self):
        return f"DeviceMesh({self.shape})"


def make_mesh(axes: Dict[str, int], devices=None) -> DeviceMesh:
    """Build a DeviceMesh with the given {axis_name: size} layout.

    Sizes must multiply to the device count (a size of -1 is inferred). Axes are
    laid out in the order given; put the highest-bandwidth-demand axis (tp/sp)
    innermost so it maps to the tightest ICI ring.
    """
    import jax
    import numpy as onp
    from jax.sharding import Mesh
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    names = list(axes.keys())
    sizes = list(axes.values())
    if sizes.count(-1) > 1:
        raise MXNetError("at most one mesh axis may be -1")
    known = 1
    for s in sizes:
        if s != -1:
            known *= s
    if -1 in sizes:
        if n % known:
            raise MXNetError(f"cannot infer axis: {n} devices not divisible by {known}")
        sizes[sizes.index(-1)] = n // known
    elif known != n:
        raise MXNetError(f"mesh {dict(zip(names, sizes))} needs {known} devices, "
                         f"have {n}")
    try:
        from jax.experimental import mesh_utils
        dev_array = mesh_utils.create_device_mesh(tuple(sizes), devices=devices)
    except Exception:
        dev_array = onp.asarray(devices).reshape(tuple(sizes))
    return DeviceMesh(Mesh(dev_array, tuple(names)))


def carve_slices(sizes: Sequence[int], devices=None):
    """Partition the visible device set into gang-scheduled slices.

    ``sizes`` are per-slice device counts, carved contiguously from
    ``devices`` (default: ``jax.devices()``) in order — contiguous ids map
    to the tightest ICI neighborhoods on a real pod slice. Asymmetric sizes
    are allowed (a 4-chip slice next to two singles), and the sizes need not
    cover every device: the leftover tail stays uncarved (available for a
    later ``carve_slices`` call or single-chip replicas). Returns a list of
    device lists, one per slice.

    Raises MXNetError when a size is < 1 or the sizes oversubscribe the
    device set — a slice plan that silently wrapped around would
    gang-schedule two "slices" onto the same chips.
    """
    import jax
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    sizes = [int(s) for s in sizes]
    if not sizes:
        raise MXNetError("carve_slices needs at least one slice size")
    for s in sizes:
        if s < 1:
            raise MXNetError(f"slice sizes must be >= 1, got {s} in {sizes}")
    if sum(sizes) > len(devices):
        raise MXNetError(
            f"slice plan {sizes} needs {sum(sizes)} devices, only "
            f"{len(devices)} visible — slices must never share chips")
    out = []
    off = 0
    for s in sizes:
        out.append(devices[off:off + s])
        off += s
    return out


def current_mesh() -> Optional[DeviceMesh]:
    stack = getattr(_current, "stack", None)
    return stack[-1] if stack else None


class batch_axes:
    """Publish, for the length of a trace on this thread, that dimension 0
    of the model's input is divided over ``axes`` of ``mesh``.

    ``ParallelTrainStep`` holds it around the trace of its model, and only
    where those axes span more than one device. An op that makes data of the
    batch's shape out of nothing (``ops/nn.py:dropout``'s mask) reads it with
    :func:`current_batch_axes` and makes each shard's part on that shard.
    ``on_draw(kind)`` tells the publisher what such an op did."""

    def __init__(self, mesh: DeviceMesh, axes: Tuple[str, ...], on_draw):
        self.mesh = mesh
        self.axes = tuple(axes)
        self.on_draw = on_draw

    @property
    def size(self) -> int:
        n = 1
        for a in self.axes:
            n *= self.mesh.axis_size(a)
        return n

    def __enter__(self):
        stack = getattr(_current, "batch_axes", None)
        if stack is None:
            stack = _current.batch_axes = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        _current.batch_axes.pop()


def current_batch_axes() -> Optional[batch_axes]:
    stack = getattr(_current, "batch_axes", None)
    return stack[-1] if stack else None


def replicated(mesh: DeviceMesh):
    return mesh.replicated()


def shard_spec(*spec):
    """PartitionSpec shorthand."""
    from jax.sharding import PartitionSpec as P
    return P(*spec)
