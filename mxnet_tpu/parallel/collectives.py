"""The communication backend: XLA collectives over ICI/DCN.

Replaces the reference's three comm paths (SURVEY.md §2.3/§5):
  - NCCL grouped reduce/broadcast (src/kvstore/kvstore_nccl.h:285,402)
  - CommDevice P2P GPU reduce tree (src/kvstore/comm.h:452, comm_tree.h:50)
  - ps-lite ZPush/ZPull parameter server + scheduler control plane
    (src/kvstore/kvstore_dist.h:50-140, kvstore_dist_server.h:52)

Two layers:

1. **In-program collectives** — used inside shard_map'd/pjit'd computations;
   lower to ICI (intra-slice) or DCN (cross-slice) collective ops chosen by XLA
   from the mesh axis. These are the building blocks ring_attention and custom
   kernels use. Data-parallel gradient reduction normally needs NONE of these
   explicitly: GSPMD inserts the all-reduce implied by the shardings.

2. **Host-level control plane** — barrier / broadcast_from_root over
   jax.distributed, replacing the ps-lite scheduler (rank/size/barrier,
   kvstore_dist.h:106-112). On a single controller these are no-ops.
"""
from __future__ import annotations

from typing import Optional

__all__ = ["all_reduce", "all_gather", "reduce_scatter", "ppermute", "all_to_all",
           "axis_index", "axis_size", "barrier", "broadcast_from_root",
           "initialize_distributed", "rank", "num_workers"]


# ---------------------------------------------------------------------------
# in-program collectives (use inside shard_map; axis_name = a mesh axis)
# ---------------------------------------------------------------------------
def all_reduce(x, axis_name: str, op: str = "sum"):
    """AllReduce across a mesh axis (ncclAllReduce analog, XLA AllReduce on ICI)."""
    import jax
    if op == "sum":
        return jax.lax.psum(x, axis_name)
    if op == "mean":
        return jax.lax.pmean(x, axis_name)
    if op == "max":
        return jax.lax.pmax(x, axis_name)
    if op == "min":
        return jax.lax.pmin(x, axis_name)
    raise ValueError(f"unsupported all_reduce op {op!r}")


def all_gather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    """AllGather across a mesh axis (XLA AllGather)."""
    import jax
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str, axis: int = 0):
    """ReduceScatter: psum then keep this shard (XLA ReduceScatter)."""
    import jax
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def ppermute(x, axis_name: str, perm):
    """Point-to-point ring permute (XLA CollectivePermute over ICI links)."""
    import jax
    return jax.lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name: str, split_axis: int, concat_axis: int):
    """AllToAll (expert-parallel dispatch / Ulysses sequence exchange)."""
    import jax
    return jax.lax.all_to_all(x, axis_name, split_axis, concat_axis, tiled=True)


def axis_index(axis_name: str):
    import jax
    return jax.lax.axis_index(axis_name)


def axis_size(axis_name: str):
    import jax
    return jax.lax.psum(1, axis_name)


# ---------------------------------------------------------------------------
# host-level control plane (ps-lite scheduler analog)
# ---------------------------------------------------------------------------
def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None):
    """Join the multi-host job (jax.distributed; replaces DMLC_PS_ROOT_URI/
    DMLC_ROLE env bootstrapping, tools/launch.py).

    jax.distributed.initialize() must run before any backend-initializing API,
    so the already-initialized check reads the distributed client state rather
    than calling jax.process_count() (which would initialize the backend and
    make a later initialize() raise).
    """
    import jax
    import os
    if jax.distributed.is_initialized():
        return  # already initialized by the launcher
    if coordinator_address is None and "MXNET_TPU_COORDINATOR" in os.environ:
        # env bootstrapping written by tools/launch.py (the DMLC_PS_ROOT_URI/
        # DMLC_NUM_WORKER/DMLC_ROLE analog); missing count/id fall through as
        # None so jax.distributed auto-detection still applies
        coordinator_address = os.environ["MXNET_TPU_COORDINATOR"]
        if num_processes is None and "MXNET_TPU_NUM_WORKERS" in os.environ:
            num_processes = int(os.environ["MXNET_TPU_NUM_WORKERS"])
        if process_id is None and "MXNET_TPU_WORKER_ID" in os.environ:
            process_id = int(os.environ["MXNET_TPU_WORKER_ID"])
    if coordinator_address is not None:
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)


def rank() -> int:
    import jax
    return jax.process_index()


def num_workers() -> int:
    import jax
    return jax.process_count()


def barrier(name: str = "mxnet_tpu_barrier"):
    """Global host barrier (ps-lite Barrier analog)."""
    import jax
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)


def broadcast_from_root(pytree):
    """Broadcast host-local values from process 0 to all processes (the
    parameter-broadcast step of dist training; kvstore_dist.h Init path)."""
    import jax
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        return multihost_utils.broadcast_one_to_all(pytree)
    return pytree
