"""Core substrate: Context (device model), dtype utilities, registry, env config.

TPU-native re-design of the reference's device & config layers:
  - ``Context`` mirrors mxnet ``Context{kCPU,kGPU,kCPUPinned}`` (include/mxnet/base.h:90-96)
    but maps onto JAX/PJRT devices; ``tpu`` is the accelerator device type and ``gpu`` is
    kept as a compatibility alias for it so reference scripts run unchanged.
  - Config mirrors the reference's ~88 MXNET_* env vars read via dmlc::GetEnv
    (docs/static_site/src/pages/api/faq/env_var.md) with one typed registry.
  - The generic registry mirrors dmlc registry patterns used for ops/optimizers/initializers.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, List, Optional

import numpy as onp

__all__ = [
    "MXNetError", "Context", "cpu", "gpu", "tpu", "current_context", "num_gpus",
    "num_tpus", "Registry", "env", "DTypes",
]


class MXNetError(RuntimeError):
    """Framework-level error (parity with dmlc::Error surfaced as MXNetError)."""


# ---------------------------------------------------------------------------
# Typed environment-config registry (replaces scattered dmlc::GetEnv reads).
# ---------------------------------------------------------------------------
class _EnvConfig:
    """Thin facade over mxnet_tpu.config — the single flag registry (that
    module imports this one, so the delegation is lazy)."""

    def register(self, name: str, default: Any, typ: type = str,
                 doc: str = "") -> None:
        from . import config
        config.register(name, default, typ, doc)

    def get(self, name: str, default: Any = None) -> Any:
        from . import config
        return config.get(name, default)

    def list_vars(self) -> Dict[str, tuple]:
        from . import config
        return {n: (config._REGISTRY[n]["default"], config._REGISTRY[n]["type"],
                    config._REGISTRY[n]["doc"]) for n in config.list_flags()}


env = _EnvConfig()


# ---------------------------------------------------------------------------
# Context: device abstraction over PJRT devices.
# ---------------------------------------------------------------------------
class Context:
    """Execution device. Parity surface: include/mxnet/base.h:90 (Context struct) and
    python/mxnet/context.py. ``gpu`` is an alias of the accelerator backend so that
    reference scripts written for CUDA devices run on TPU unmodified."""

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 5}
    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in self.devstr2type:
            raise MXNetError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- identity ----------------------------------------------------------
    @property
    def device_typeid(self) -> int:
        return self.devstr2type[self.device_type]

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self._canonical_type() == other._canonical_type()
                and self.device_id == other.device_id)

    def _canonical_type(self) -> str:
        # gpu/tpu both resolve to the accelerator platform
        return "tpu" if self.device_type in ("gpu", "tpu") else "cpu"

    def __hash__(self):
        return hash((self._canonical_type(), self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- JAX mapping -------------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete PJRT device. device_id indexes this process's
        *addressable* devices — under multi-process (jax.distributed) each
        worker addresses its own chips, like each reference worker its own
        GPUs; global devices are reachable only through sharded computations."""
        import jax
        if self._canonical_type() == "cpu":
            devs = jax.local_devices(backend="cpu")
        else:
            devs = _accelerator_devices()
            if not devs:
                raise MXNetError(
                    f"{self}: JAX found no accelerator (default backend "
                    f"{jax.default_backend()!r}); a CPU run uses mx.cpu()")
        if self.device_id >= len(devs):
            raise MXNetError(f"{self}: only {len(devs)} device(s) available")
        return devs[self.device_id]

    @classmethod
    def from_jax_device(cls, dev) -> "Context":
        import jax
        if dev.platform == "cpu":
            local = jax.local_devices(backend="cpu")
            # device ids are global under multi-process; Context ids are local
            return Context("cpu", local.index(dev) if dev in local else dev.id)
        accel = _accelerator_devices()
        return Context("tpu", accel.index(dev) if dev in accel else dev.id)

    # -- default-context scoping (python/mxnet/context.py Context.__enter__) --
    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._default_ctx.stack.pop()
        return False

    def empty_cache(self):  # GPU pool clear analog; PJRT manages HBM pooling
        import gc
        gc.collect()


def _accelerator_devices() -> List:
    import jax
    for platform in ("tpu", None):
        try:
            devs = jax.local_devices(backend=platform)
        except RuntimeError:
            continue
        non_cpu = [d for d in devs if d.platform != "cpu"]
        if non_cpu:
            return non_cpu
        if platform is None:
            return []
    return []


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Compatibility alias: accelerator device (TPU on this stack)."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def num_gpus() -> int:
    """Number of accelerator chips visible (parity: mx.context.num_gpus)."""
    return len(_accelerator_devices())


num_tpus = num_gpus


def current_context() -> Context:
    stack = getattr(Context._default_ctx, "stack", None)
    if stack:
        return stack[-1]
    return Context("cpu", 0)


# ---------------------------------------------------------------------------
# dtype utilities
# ---------------------------------------------------------------------------
class DTypes:
    """dtype canonicalisation. bf16 is first-class on TPU (reference: fp16 via AMP)."""
    _ALIASES = {
        "float": "float32", "double": "float64", "half": "float16",
        "bfloat16": "bfloat16", "bf16": "bfloat16", "fp16": "float16",
        "int": "int32", "long": "int64", "bool": "bool_",
    }

    @staticmethod
    def canonical(dtype) -> str:
        import jax.numpy as jnp
        if dtype is None:
            return "float32"
        if isinstance(dtype, str):
            name = DTypes._ALIASES.get(dtype, dtype)
            return "bool_" if name == "bool" else name
        if dtype is bool:
            return "bool_"
        if dtype in (int,):
            return "int64"
        if dtype in (float,):
            return "float64"
        name = jnp.dtype(dtype).name
        return DTypes._ALIASES.get(name, name)

    @staticmethod
    def jnp(dtype):
        import jax.numpy as jnp
        name = DTypes.canonical(dtype)
        if name == "bfloat16":
            return jnp.bfloat16
        if name == "bool_":
            return jnp.bool_
        return jnp.dtype(name)


# ---------------------------------------------------------------------------
# Generic registry (dmlc::Registry analog)
# ---------------------------------------------------------------------------
class Registry:
    def __init__(self, name: str):
        self.name = name
        self._entries: Dict[str, Any] = {}

    def register(self, name: Optional[str] = None, override: bool = False) -> Callable:
        def deco(obj):
            key = (name or getattr(obj, "__name__", str(obj))).lower()
            if key in self._entries and not override:
                raise MXNetError(f"{self.name} registry: duplicate entry {key!r}")
            self._entries[key] = obj
            return obj
        return deco

    def get(self, name: str):
        key = name.lower()
        if key not in self._entries:
            raise MXNetError(
                f"{self.name} registry: unknown entry {name!r}; "
                f"known: {sorted(self._entries)}")
        return self._entries[key]

    def __contains__(self, name):
        return name.lower() in self._entries

    def list(self):
        return sorted(self._entries)


def check_call(ok: bool, msg: str = ""):
    if not ok:
        raise MXNetError(msg)
