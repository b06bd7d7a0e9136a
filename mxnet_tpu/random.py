"""Global RNG state + seeding (parity: python/mxnet/random.py, mx.random.seed).

The reference keeps per-device sampler states (include/mxnet/random_generator.h);
here a threefry key chain per thread. During HybridBlock tracing the key source is
overridden by the trace context so dropout/samplers become pure functions of a key
argument threaded through the compiled computation.
"""
from __future__ import annotations

import threading
from typing import Callable, Optional

__all__ = ["seed", "take_key", "push_key_source", "pop_key_source",
           "get_state", "set_state"]


class _State(threading.local):
    def __init__(self):
        self.key = None
        self.sources = []  # stack of callables returning keys (trace contexts)


_STATE = _State()
_DEFAULT_SEED = 0


_PRNG_IMPLS = ("threefry2x32", "rbg", "unsafe_rbg")


def _prng_impl():
    """PRNG implementation (MXNET_PRNG_IMPL): threefry2x32 | rbg |
    unsafe_rbg | auto ('threefry' accepted as a threefry2x32 alias).

    'auto' picks the hardware-friendly rbg generator on TPU (measured +13%
    BERT-base pretraining throughput — threefry burns MXU-adjacent cycles
    generating dropout bits) and threefry on CPU, keeping test runs on the
    virtual CPU mesh bit-reproducible with older snapshots.

    An rbg draw is one ``rng-bit-generator`` op whose output is a function
    of the key and the *whole* shape: the SPMD partitioner cannot give a
    device its slice of it without drawing all of it (threefry's bits are
    elementwise in a counter, which divides). So a draw that should be
    divided over devices is made per shard by whoever knows the division:
    ``ops/nn.py:_dropout_bits`` under a data-parallel train step."""
    from . import config
    from .base import MXNetError
    impl = config.get("MXNET_PRNG_IMPL", "auto")
    if impl == "threefry":
        return "threefry2x32"
    if impl != "auto":
        if impl not in _PRNG_IMPLS:
            raise MXNetError(
                f"MXNET_PRNG_IMPL={impl!r}: expected one of "
                f"{('auto', 'threefry') + _PRNG_IMPLS}")
        return impl
    import jax
    try:
        return "rbg" if jax.default_backend() not in ("cpu",) else "threefry2x32"
    except RuntimeError:  # backend not initialized yet
        return "threefry2x32"


def seed(seed_state: int, ctx="all"):
    import jax
    impl = _prng_impl()
    if impl == "threefry2x32":
        _STATE.key = jax.random.PRNGKey(seed_state)
    else:
        _STATE.key = jax.random.key(seed_state, impl=impl)


def take_key():
    """Return a fresh PRNG key (splitting the global chain)."""
    if _STATE.sources:
        return _STATE.sources[-1]()
    import jax
    if _STATE.key is None:
        seed(_DEFAULT_SEED)
    _STATE.key, sub = jax.random.split(_STATE.key)
    return sub


def get_state():
    """Serializable snapshot of this thread's key chain (the checkpoint
    surface): ``{"impl": str, "typed": 0|1, "data": uint32 ndarray}``.
    Restoring it with :func:`set_state` makes the subsequent ``take_key()``
    stream identical — the property crash/restore bitwise-equality needs."""
    import jax
    import numpy as onp
    if _STATE.key is None:
        seed(_DEFAULT_SEED)
    k = _STATE.key
    try:
        typed = jax.numpy.issubdtype(k.dtype, jax.dtypes.prng_key)
    except (AttributeError, TypeError):
        typed = False
    if typed:
        return {"impl": str(jax.random.key_impl(k)), "typed": 1,
                "data": onp.asarray(jax.random.key_data(k))}
    return {"impl": "threefry2x32", "typed": 0, "data": onp.asarray(k)}


def set_state(state):
    """Restore a :func:`get_state` snapshot into this thread's key chain."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    data = jnp.asarray(onp.asarray(state["data"]), dtype=jnp.uint32)
    if int(state.get("typed", 0)):
        _STATE.key = jax.random.wrap_key_data(data, impl=str(state["impl"]))
    else:
        _STATE.key = data


def push_key_source(fn: Callable):
    _STATE.sources.append(fn)


def pop_key_source():
    _STATE.sources.pop()


_SAMPLERS = ("normal", "uniform", "randn", "randint", "poisson",
             "exponential", "gamma", "multinomial", "negative_binomial",
             "bernoulli", "shuffle")


def __getattr__(name):
    """Sampler parity surface (python/mxnet/random.py re-exports the ndarray
    samplers): delegate the allowlisted sampler names to nd.random so
    mx.random.normal(...) works like the reference — an open delegation
    would leak nd.random's helper imports onto this module."""
    if name in _SAMPLERS:
        from .ndarray import random as _nd_random
        return getattr(_nd_random, name)
    raise AttributeError(f"module 'mxnet_tpu.random' has no attribute {name!r}")
