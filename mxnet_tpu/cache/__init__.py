"""mxnet_tpu.cache — persistent, content-addressed compiled-artifact caches.

The first (and defining) member is :mod:`executable_cache`: serialized XLA
executables keyed by (StableHLO fingerprint, device topology, runtime
versions), stored on disk so a restarted or scaled-out replica starts
compile-free. See ROADMAP item 2 and the "Elastic fleet runbook" in
RESILIENCE.md.
"""
from __future__ import annotations

import os

from . import executable_cache

__all__ = ["executable_cache", "enable_compile_cache"]

# fixed: the directory is part of every entry's key, so one that moved
# (a temp dir, a pid, a date) would never hit
_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point
    (chipbench, chip_smoke.py, benchmark/*.py) before its first compile.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already writes there and
    nothing is set in code; otherwise the cache lives in ``.jax_cache`` at
    the root of the checkout. Returns the directory in use. Tests never
    call this: tier-1 runs without a compile cache."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        import jax
        d = _COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", d)
    return d
