"""Runtime feature detection (parity: python/mxnet/runtime.py over the
include/mxnet/libinfo.h:145-197 feature enum). Features reflect the TPU stack."""
from __future__ import annotations

from collections import namedtuple

Feature = namedtuple("Feature", ["name", "enabled"])

_FEATURES = None


def _detect():
    global _FEATURES
    if _FEATURES is not None:
        return _FEATURES
    import jax
    feats = {}
    platforms = {d.platform for d in jax.devices()}
    feats["TPU"] = any(p not in ("cpu",) for p in platforms)
    feats["CUDA"] = False
    feats["CUDNN"] = False
    feats["NCCL"] = False
    feats["XLA"] = True
    feats["PALLAS"] = True
    feats["MKLDNN"] = False
    feats["OPENCV"] = _has_module("cv2")
    feats["BLAS_OPEN"] = True
    feats["DIST_KVSTORE"] = True            # jax.distributed multi-host
    feats["INT64_TENSOR_SIZE"] = True
    feats["SIGNAL_HANDLER"] = True
    feats["F16C"] = True
    feats["BF16"] = True
    feats["PROFILER"] = True
    feats["NATIVE_ENGINE"] = _has_native_engine()
    _FEATURES = {k: Feature(k, v) for k, v in feats.items()}
    return _FEATURES


def _has_module(name):
    import importlib.util
    return importlib.util.find_spec(name) is not None


def _has_native_engine():
    try:
        from . import native
        return native.get_lib() is not None
    except Exception:
        return False


class Features(dict):
    def __init__(self):
        super().__init__(_detect())

    def is_enabled(self, name):
        return self[name.upper()].enabled

    def __repr__(self):
        return f"[{', '.join(f'✔ {k}' if v.enabled else f'✖ {k}' for k, v in self.items())}]"


def feature_list():
    return list(_detect().values())


libinfo_features = feature_list


# ---------------------------------------------------------------------------
# where a measurement runs (chipbench, chip_smoke.py, benchmark/*.py)
# ---------------------------------------------------------------------------
DEVICE_ROW_KEYS = ("platform", "device_kind", "device_count")


def device_row() -> dict:
    """The device as JAX reports it, under DEVICE_ROW_KEYS; every row a
    measurement prints carries these, so a CPU timing can never pass for a
    chip's."""
    import jax
    devs = jax.devices()
    return dict(zip(DEVICE_ROW_KEYS,
                    (devs[0].platform, devs[0].device_kind, len(devs))))


def measurement_context():
    """The context a measurement places its work on: ``tpu(0)`` when JAX's
    default backend is the TPU, ``cpu(0)`` only when the user asked for a
    CPU run with ``JAX_PLATFORMS=cpu``. A host where JAX found no chip and
    nobody said so is an error, not a CPU run."""
    import os
    import jax
    from .base import MXNetError, cpu, tpu
    backend = jax.default_backend()
    if backend == "tpu":
        return tpu(0)
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return cpu(0)
    raise MXNetError(
        f"JAX's default backend is {backend!r}, not the TPU; set "
        "JAX_PLATFORMS=cpu to run on the CPU on purpose")
