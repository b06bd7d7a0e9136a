"""Native runtime components: dependency engine, recordio, image pipeline.

Parity (SURVEY.md §2.1/§2.4): the reference's engine (src/engine/
threaded_engine.{h,cc}) schedules *all* execution; on TPU the compute path is
PJRT/XLA-async, so the native engine here schedules the host side — IO
prefetch, decode workers, checkpoint writers — with the same per-variable
read/write dependency semantics. recordio.cc implements the dmlc recordio
framing byte-compatibly; image_pipeline.cc is the ImageRecordIter stack
(decode→augment→batch→prefetch threads over OpenCV).

Built with `make` on first use in every process (ctypes bindings — no
pybind11 in this image); `make` is a no-op when the library is newer than its
sources, and rebuilds one that is not, so a stale binary never outlives them.
No `.so` is tracked in git. Falls back gracefully: `available()` is False if
the toolchain or a build dependency is missing, and the Python
implementations take over.
"""
from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libmxtpu_native.so")
_lock = threading.Lock()
_lib = None
_build_error = None


def _build():
    global _build_error
    try:
        # one make at a time across processes (test workers start together):
        # a second one would otherwise load a half-written library
        with open(os.path.join(_DIR, ".build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            res = subprocess.run(["make", "-C", _DIR], capture_output=True,
                                 text=True, timeout=300)
        if res.returncode != 0:
            _build_error = res.stderr[-2000:]
            return False
        return True
    except Exception as e:  # noqa: BLE001
        _build_error = str(e)
        return False


def get_lib():
    """Load (building if needed) the native library; None if unavailable."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        if not _build():
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:  # pragma: no cover
            global _build_error
            _build_error = str(e)
            return None
        _configure(lib)
        _lib = lib
        return _lib


def available() -> bool:
    return get_lib() is not None


def build_error():
    return _build_error


def _configure(lib):
    c = ctypes
    lib.mxtpu_engine_create.restype = c.c_void_p
    lib.mxtpu_engine_create.argtypes = [c.c_int]
    lib.mxtpu_engine_destroy.argtypes = [c.c_void_p]
    lib.mxtpu_engine_new_var.restype = c.c_int64
    lib.mxtpu_engine_new_var.argtypes = [c.c_void_p]
    lib.mxtpu_engine_push.argtypes = [
        c.c_void_p, c.CFUNCTYPE(None, c.c_void_p), c.c_void_p,
        c.POINTER(c.c_int64), c.c_int, c.POINTER(c.c_int64), c.c_int]
    lib.mxtpu_engine_push_ex.argtypes = [
        c.c_void_p, c.CFUNCTYPE(None, c.c_void_p), c.c_void_p,
        c.POINTER(c.c_int64), c.c_int, c.POINTER(c.c_int64), c.c_int,
        c.c_int, c.c_int, c.c_int]
    lib.mxtpu_engine_wait_for_var.argtypes = [c.c_void_p, c.c_int64]
    lib.mxtpu_engine_wait_all.argtypes = [c.c_void_p]
    lib.mxtpu_engine_stats.argtypes = [c.c_void_p, c.POINTER(c.c_int64)]
    lib.mxtpu_engine_last_error.restype = c.c_char_p
    lib.mxtpu_engine_last_error.argtypes = [c.c_void_p]
    lib.mxtpu_engine_set_error.argtypes = [c.c_void_p, c.c_char_p]
    lib.mxtpu_engine_clear_error.argtypes = [c.c_void_p]

    lib.mxtpu_recio_writer_open.restype = c.c_void_p
    lib.mxtpu_recio_writer_open.argtypes = [c.c_char_p]
    lib.mxtpu_recio_write.restype = c.c_int64
    lib.mxtpu_recio_write.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
    lib.mxtpu_recio_writer_close.argtypes = [c.c_void_p]
    lib.mxtpu_recio_reader_open.restype = c.c_void_p
    lib.mxtpu_recio_reader_open.argtypes = [c.c_char_p]
    lib.mxtpu_recio_read.restype = c.c_int64
    lib.mxtpu_recio_read.argtypes = [c.c_void_p, c.POINTER(c.c_char_p)]
    lib.mxtpu_recio_seek.argtypes = [c.c_void_p, c.c_int64]
    lib.mxtpu_recio_tell.restype = c.c_int64
    lib.mxtpu_recio_tell.argtypes = [c.c_void_p]
    lib.mxtpu_recio_reader_close.argtypes = [c.c_void_p]

    if hasattr(lib, "mxtpu_impipe_create"):
        lib.mxtpu_impipe_create.restype = c.c_void_p
        lib.mxtpu_impipe_create.argtypes = [
            c.c_char_p, c.c_int, c.c_int, c.c_int, c.c_int, c.c_int, c.c_int,
            c.c_int, c.c_int, c.POINTER(c.c_float), c.POINTER(c.c_float),
            c.c_int, c.c_int, c.c_int]
        lib.mxtpu_impipe_next.restype = c.c_int
        lib.mxtpu_impipe_next.argtypes = [c.c_void_p,
                                          c.POINTER(c.c_float),
                                          c.POINTER(c.c_float)]
        lib.mxtpu_impipe_reset.argtypes = [c.c_void_p]
        lib.mxtpu_impipe_destroy.argtypes = [c.c_void_p]


# ---------------------------------------------------------------------------
# Python-facing wrappers
# ---------------------------------------------------------------------------
class NativeEngine:
    """Host-side dependency engine (Engine::PushAsync/WaitForVar/WaitForAll
    semantics, engine.h:117-318). Python callables run on C++ worker threads."""

    def __init__(self, num_workers=4):
        lib = get_lib()
        if lib is None:
            raise RuntimeError(f"native library unavailable: {_build_error}")
        self._lib = lib
        self._h = lib.mxtpu_engine_create(num_workers)
        self._cbs = {}          # keep callbacks alive until executed
        self._cb_lock = threading.Lock()
        self._next_id = 0
        self._cb_type = ctypes.CFUNCTYPE(None, ctypes.c_void_p)

    def new_var(self):
        return self._lib.mxtpu_engine_new_var(self._h)

    LANE_NORMAL, LANE_COPY, LANE_PRIORITY = 0, 1, 2  # FnProperty analog

    def push(self, fn, read_vars=(), write_vars=(), device=0, lane=0,
             priority=0):
        """PushAsync. ``device``/``lane`` route to a dedicated worker pool
        (ThreadedEnginePerDevice); ``priority`` orders dispatch in-pool."""
        with self._cb_lock:
            cb_id = self._next_id
            self._next_id += 1

        def trampoline(_arg, _id=cb_id):
            try:
                fn()
            except Exception as e:  # noqa: BLE001
                self._lib.mxtpu_engine_set_error(self._h, str(e).encode())
            finally:
                with self._cb_lock:
                    self._cbs.pop(_id, None)

        cfunc = self._cb_type(trampoline)
        with self._cb_lock:
            self._cbs[cb_id] = cfunc
        reads = (ctypes.c_int64 * len(read_vars))(*read_vars)
        writes = (ctypes.c_int64 * len(write_vars))(*write_vars)
        if device == 0 and lane == 0 and priority == 0:
            self._lib.mxtpu_engine_push(self._h, cfunc, None, reads,
                                        len(read_vars), writes,
                                        len(write_vars))
        else:
            self._lib.mxtpu_engine_push_ex(self._h, cfunc, None, reads,
                                           len(read_vars), writes,
                                           len(write_vars), device, lane,
                                           priority)

    def _check_error(self):
        err = self._lib.mxtpu_engine_last_error(self._h)
        if err:
            self._lib.mxtpu_engine_clear_error(self._h)
            raise RuntimeError(err.decode())

    def wait_for_var(self, var):
        self._lib.mxtpu_engine_wait_for_var(self._h, var)
        self._check_error()

    def wait_all(self):
        self._lib.mxtpu_engine_wait_all(self._h)
        self._check_error()

    def stats(self):
        """Debug counters (MXNET_ENGINE_DEBUG accounting analog):
        pushed/completed totals, live pending gauge, worker-pool count."""
        buf = (ctypes.c_int64 * 4)()
        self._lib.mxtpu_engine_stats(self._h, buf)
        return {"pushed": buf[0], "completed": buf[1], "pending": buf[2],
                "pools": buf[3]}

    def close(self):
        if self._h:
            self._lib.mxtpu_engine_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass
