"""mx.rtc: runtime kernel compilation (parity: python/mxnet/rtc.py:41
CudaModule over src/common/rtc.cc NVRTC).

TPU-native mapping: the runtime kernel language is **Pallas** (the TPU
equivalent of writing raw CUDA), and the runtime compiler is XLA/Mosaic
instead of NVRTC. ``PallasModule`` takes kernel SOURCE TEXT (Python defining
Pallas kernel bodies over ``Ref``s), compiles it at runtime, and exposes
launchable kernels — the CudaModule(source).get_kernel(name).launch(...)
workflow with grids instead of CUDA block/thread dims.

Example::

    mod = rtc.PallasModule('''
    def axpy(x_ref, y_ref, o_ref):
        o_ref[...] = 2.0 * x_ref[...] + y_ref[...]
    ''')
    k = mod.get_kernel("axpy")
    out = k.launch([x, y], out_shapes=[x.shape])
"""
from __future__ import annotations

from .base import MXNetError
from .ndarray.ndarray import NDArray

__all__ = ["PallasModule", "Kernel"]


class Kernel:
    """A launchable runtime-compiled kernel (rtc.py CudaKernel analog)."""

    def __init__(self, fn, name):
        self._fn = fn
        self._name = name
        self._cache = {}

    def launch(self, args, ctx=None, grid=None, out_shapes=None,
               out_dtypes=None, **pallas_kwargs):
        """Run the kernel (CudaKernel.launch analog). ``grid`` replaces
        grid_dims/block_dims — XLA/Mosaic owns the intra-block schedule.

        args: input NDArrays; out_shapes: list of output shapes (required);
        out_dtypes: matching dtypes (default: dtype of the first input)."""
        import jax
        import jax.numpy as jnp
        import numpy as onp
        from jax.experimental import pallas as pl

        if out_shapes is None:
            raise MXNetError("launch requires out_shapes")
        arrays = [a.data if isinstance(a, NDArray) else jnp.asarray(a)
                  for a in args]
        if out_dtypes is None:
            out_dtypes = [arrays[0].dtype] * len(out_shapes)
        # Mosaic compiles for the TPU only; anywhere else the kernel runs
        # in the interpreter. Decided by where the first input lives, since
        # that is where the call will run
        interpret = any(d.platform != "tpu" for d in arrays[0].devices())
        key = (tuple(tuple(s) for s in out_shapes),
               tuple(str(d) for d in out_dtypes),
               None if grid is None else tuple(grid), interpret,
               # values matter, not just names: a different in_specs/out_specs
               # must not reuse the stale executable
               tuple(sorted((k, repr(v)) for k, v in pallas_kwargs.items())))
        call = self._cache.get(key)
        if call is None:
            out_shape = [jax.ShapeDtypeStruct(tuple(s), onp.dtype(d))
                         for s, d in zip(out_shapes, out_dtypes)]
            shape_arg = out_shape if len(out_shape) > 1 else out_shape[0]
            call = jax.jit(pl.pallas_call(
                self._fn, out_shape=shape_arg,
                **({"grid": tuple(grid)} if grid else {}),
                interpret=interpret, **pallas_kwargs))
            self._cache[key] = call
        outs = call(*arrays)
        ctx = ctx or (args[0].context if isinstance(args[0], NDArray)
                      else None)
        if isinstance(outs, (list, tuple)):
            return [NDArray(o, ctx=ctx) for o in outs]
        return NDArray(outs, ctx=ctx)


class PallasModule:
    """Runtime-compiled kernel module from source text (CudaModule analog,
    rtc.py:41). ``exports`` optionally restricts which names are kernels."""

    def __init__(self, source, options=(), exports=()):
        self._namespace = {}
        # the kernel source is Python-over-Pallas; give it the usual aliases
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        self._namespace.update({"jax": jax, "jnp": jnp, "pl": pl})
        try:
            exec(compile(source, "<rtc>", "exec"), self._namespace)
        except SyntaxError as e:
            raise MXNetError(f"PallasModule: kernel source failed to "
                             f"compile: {e}") from e
        self._exports = set(exports) if exports else None

    def get_kernel(self, name, signature=None):
        """Look up a kernel body by name (signature accepted for API parity —
        shapes/dtypes bind at launch, the XLA way)."""
        if self._exports is not None and name not in self._exports:
            raise MXNetError(f"kernel {name!r} not exported")
        fn = self._namespace.get(name)
        if fn is None or not callable(fn):
            raise MXNetError(f"kernel {name!r} not found in module source")
        return Kernel(fn, name)
