"""NDArray: the framework tensor.

Parity surface: include/mxnet/ndarray.h:82 (NDArray), src/ndarray/ndarray.cc
(WaitToRead:2175, Save:1679/Load:1802, SyncCopyFromCPU:1957) and the Python
frontend python/mxnet/ndarray/ndarray.py.

TPU-native design: an NDArray owns a ``jax.Array`` (a PJRT buffer in HBM or host
memory). The reference's async dependency engine (per-var read/write queues,
src/engine/threaded_engine.h) is subsumed by PJRT's asynchronous dispatch: every
op returns immediately with a future-backed buffer, ``wait_to_read`` ==
``block_until_ready``, and asynchronous errors surface at sync points exactly like
the reference's per-var exception propagation (threaded_engine.cc:422-427).
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple, Union

import numpy as onp

from ..base import Context, DTypes, MXNetError, current_context

__all__ = ["NDArray", "array", "_wrap_output"]


def _jnp():
    import jax.numpy as jnp
    return jnp


class NDArray:
    """Multi-dimensional array backed by a PJRT buffer; asynchronous by construction."""

    __slots__ = ("_data", "_ctx", "_grad", "_grad_req", "_tape_node", "_tape_index",
                 "_is_predicate", "__weakref__")

    # Let NDArray win binary ops against numpy arrays
    __array_priority__ = 1000.0

    def __init__(self, data, ctx: Optional[Context] = None, dtype=None):
        import jax
        import jax.numpy as jnp
        if isinstance(data, NDArray):
            data = data._data
        if dtype is not None:
            dtype = DTypes.jnp(dtype)
        if isinstance(data, jax.Array):
            arr = data.astype(dtype) if dtype is not None and data.dtype != dtype else data
            if ctx is not None:
                dev = ctx.jax_device()
                if _single_device_of(arr) != dev:
                    arr = jax.device_put(arr, dev)
        else:
            was_ndarray = isinstance(data, onp.ndarray)
            npdata = onp.asarray(data, dtype=None if dtype is None else onp.dtype("float32")
                                 if dtype == jnp.bfloat16 else dtype)
            if dtype is None:
                if not was_ndarray and npdata.dtype.kind in "iu":
                    npdata = npdata.astype(onp.float32)  # lists default to fp32
                elif npdata.dtype == onp.float64:
                    npdata = npdata.astype(onp.float32)  # fp32 default (reference)
                elif npdata.dtype == onp.int64:
                    npdata = npdata.astype(onp.int32)  # x64 disabled on this stack
            dev = (ctx or current_context()).jax_device()
            # straight to the context's device: jnp.asarray would stage the
            # host data on JAX's default device (the chip) first
            arr = jax.device_put(npdata, dev)
            if dtype is not None:
                arr = arr.astype(dtype)
        self._data = arr
        self._ctx = ctx if ctx is not None else Context.from_jax_device(
            _single_device_of(arr) or jax.devices("cpu")[0])
        self._grad = None
        self._grad_req = "null"
        self._tape_node = None
        self._tape_index = 0

    # ------------------------------------------------------------------
    # core properties
    # ------------------------------------------------------------------
    @property
    def data(self):
        """Underlying jax.Array."""
        return self._data

    def _set_data(self, arr):
        self._data = arr

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def size(self) -> int:
        return int(onp.prod(self.shape)) if self.shape else 1

    @property
    def ndim(self) -> int:
        return self._data.ndim

    @property
    def context(self) -> Context:
        return self._ctx

    ctx = context

    @property
    def stype(self) -> str:
        return "default"  # row_sparse/csr handled by sparse module wrappers

    @property
    def T(self) -> "NDArray":
        from . import transpose
        return transpose(self)

    # ------------------------------------------------------------------
    # sync / transfer (engine semantics surface)
    # ------------------------------------------------------------------
    def wait_to_read(self):
        """Block until value ready; async errors raise here (ndarray.cc:2175)."""
        self._data.block_until_ready()
        return self

    wait_to_write = wait_to_read

    def asnumpy(self) -> onp.ndarray:
        return onp.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(()).item()

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("ambiguous truth value of multi-element NDArray")

    def __len__(self):
        if not self.shape:
            raise MXNetError("len() of 0-d array")
        return self.shape[0]

    def astype(self, dtype, copy=True) -> "NDArray":
        jdt = DTypes.jnp(dtype)
        if not copy and self._data.dtype == jdt:
            return self
        from ..ops.registry import apply_op
        return apply_op("cast", self, dtype=DTypes.canonical(dtype))

    def copy(self) -> "NDArray":
        return NDArray(self._data + 0 if False else self._data, ctx=self._ctx)

    def copyto(self, other: Union["NDArray", Context]) -> "NDArray":
        import jax
        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device()), ctx=other)
        other._set_data(jax.device_put(self._data.astype(other.dtype),
                                       other.context.jax_device()))
        return other

    def as_in_context(self, ctx: Context) -> "NDArray":
        if ctx == self._ctx:
            return self
        return self.copyto(ctx)

    as_in_ctx = as_in_context

    def to_device(self, ctx):
        return self.as_in_context(ctx)

    # ------------------------------------------------------------------
    # autograd surface
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req: str = "write", stype=None):
        """Allocate a gradient buffer for this array (ndarray.py attach_grad parity)."""
        jnp = _jnp()
        if stype is not None and stype != "default":
            from ..sparse import zeros as sparse_zeros
            self._grad = sparse_zeros(stype, self.shape, ctx=self._ctx,
                                      dtype=str(self._data.dtype))
        else:
            self._grad = NDArray(jnp.zeros(self.shape, self._data.dtype),
                                 ctx=self._ctx)
        self._grad_req = grad_req

    @property
    def grad(self) -> Optional["NDArray"]:
        return self._grad

    def detach(self) -> "NDArray":
        out = NDArray(self._data, ctx=self._ctx)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    def _mask_index(self, key):
        """A same-shaped boolean NDArray index — or a comparison result,
        which carries 0/1 floats for nd parity but is tagged _is_predicate —
        is a boolean mask: np-style ``x[x > 2]`` / ``x[x > 2] = v``
        (_npi_boolean_mask_assign_* semantics). Untagged float index arrays
        are always gather indices (take semantics), even if 0/1-valued."""
        if not (isinstance(key, NDArray) and key.shape == self.shape):
            return None
        kd = key._data
        if kd.dtype == bool:
            return kd
        if getattr(key, "_is_predicate", False):
            return kd.astype(bool)
        return None

    def _big_static_int(self, key):
        """True when integer indexing must reroute through STATIC slices:
        gather/scatter index OPERANDS are int32-bounded here (x64 disabled) —
        on arrays past 2^31 elements jax's .at[...] truncates its int64 index
        request and silently corrupts — while static slice bounds live in the
        HLO as int64 (large-tensor support, test_large_array.py tier)."""
        big_arr = self._data.size > 2 ** 31 - 1
        lim = 2 ** 31 - 1

        def is_int(k):
            return isinstance(k, (int, onp.integer)) \
                and not isinstance(k, bool)

        if is_int(key):
            return big_arr or abs(key) > lim
        if isinstance(key, tuple):
            ints = [k for k in key if is_int(k)]
            return bool(ints) and (big_arr or any(abs(k) > lim for k in ints))
        return False

    def _get_big_int(self, key):
        # under jit the slice is a STATIC HLO slice (int64 bounds in the
        # proto); eager slicing would route through dynamic_slice whose index
        # operands are int32-parsed
        import jax
        import jax.numpy as jnp
        ks = key if isinstance(key, tuple) else (key,)

        def gather(data):
            squeeze = []
            for d, k in enumerate(ks):
                if isinstance(k, int):
                    kk = k if k >= 0 else k + data.shape[d]
                    sl = [slice(None)] * data.ndim
                    sl[d] = slice(kk, kk + 1)
                    data = data[tuple(sl)]
                    squeeze.append(d)
                elif not (isinstance(k, slice) and k == slice(None)):
                    raise MXNetError("large-int indexing supports int and "
                                     "':' components only")
            return jnp.squeeze(data, axis=tuple(squeeze))

        return NDArray(jax.jit(gather)(self._data), ctx=self._ctx)

    def __getitem__(self, key) -> "NDArray":
        from ..ops.registry import apply_op
        mask = self._mask_index(key)
        if mask is not None:
            return NDArray(self._data[mask], ctx=self._ctx)
        if self._big_static_int(key):
            return self._get_big_int(key)
        key = _canon_index(key)
        return apply_op("_getitem", self, key=key)

    def __setitem__(self, key, value):
        jnp = _jnp()
        mask = self._mask_index(key)
        if mask is not None:
            if isinstance(value, NDArray):
                value = value._data
            if onp.ndim(value) == 0:
                self._set_data(jnp.where(
                    mask, jnp.asarray(value, self._data.dtype), self._data))
            else:
                # non-scalar value: numpy semantics fill the masked positions
                # in row-major order (never a broadcast across the full
                # shape) — data-dependent scatter, host boundary
                host = onp.array(self.asnumpy())
                host[onp.asarray(mask)] = onp.asarray(value)
                self._set_data(jnp.asarray(host))
            return
        if self._big_static_int(key):
            k = key if isinstance(key, (int, onp.integer)) else None
            if k is None:
                raise MXNetError("large-tensor assignment supports a single "
                                 "leading int index only")
            k = int(k) if k >= 0 else int(k) + self._data.shape[0]
            v = value._data if isinstance(value, NDArray) else value
            v = jnp.asarray(v, self._data.dtype).reshape(
                (1,) + self._data.shape[1:])
            # static-slice concatenation under jit: slice bounds are int64 in
            # the HLO; eager slicing (and .at[...] scatter) overflows/
            # truncates int32 index handling on >2^31-element arrays
            import jax
            self._set_data(jax.jit(
                lambda d, vv: jnp.concatenate([d[:k], vv, d[k + 1:]]))(
                    self._data, v))
            return
        key = _canon_index(key, raw=True)
        if isinstance(value, NDArray):
            value = value._data.astype(self._data.dtype)
        if isinstance(key, tuple) and len(key) == 1 and key[0] is Ellipsis:
            if onp.isscalar(value):
                self._set_data(jnp.full(self.shape, value, self._data.dtype))
            else:
                self._set_data(jnp.broadcast_to(jnp.asarray(value, self._data.dtype),
                                                self.shape))
            return
        self._set_data(self._data.at[key].set(value))

    # ------------------------------------------------------------------
    # arithmetic dunders → registered ops (so they land on the autograd tape)
    # ------------------------------------------------------------------
    def _binary(self, other, op, scalar_op, reverse=False):
        from ..ops.registry import apply_op
        if isinstance(other, NDArray):
            a, b = (other, self) if reverse else (self, other)
            return apply_op(op, a, b)
        if isinstance(other, (onp.ndarray, list, tuple)):
            other = NDArray(other, ctx=self._ctx)
            a, b = (other, self) if reverse else (self, other)
            return apply_op(op, a, b)
        return apply_op(scalar_op, self, scalar=float(other), reverse=reverse)

    def __add__(self, o):
        return self._binary(o, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binary(o, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, o):
        return self._binary(o, "broadcast_sub", "_minus_scalar", reverse=True)

    def __mul__(self, o):
        return self._binary(o, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binary(o, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, o):
        return self._binary(o, "broadcast_div", "_div_scalar", reverse=True)

    def __mod__(self, o):
        return self._binary(o, "broadcast_mod", "_mod_scalar")

    def __rmod__(self, o):
        return self._binary(o, "broadcast_mod", "_mod_scalar", reverse=True)

    def __and__(self, o):
        return self._compare(o, "broadcast_logical_and")

    def __or__(self, o):
        return self._compare(o, "broadcast_logical_or")

    def __xor__(self, o):
        return self._compare(o, "broadcast_logical_xor")

    def __invert__(self):
        from ..ops.registry import apply_op
        return apply_op("logical_not", self)

    def __pow__(self, o):
        return self._binary(o, "broadcast_power", "_power_scalar")

    def __rpow__(self, o):
        return self._binary(o, "broadcast_power", "_power_scalar", reverse=True)

    def __matmul__(self, o):
        from ..ops.registry import apply_op
        return apply_op("matmul", self, o)

    def __neg__(self):
        from ..ops.registry import apply_op
        return apply_op("negative", self)

    def __abs__(self):
        from ..ops.registry import apply_op
        return apply_op("abs", self)

    def __iadd__(self, o):
        res = self.__add__(o)
        self._set_data(res._data)
        return self

    def __isub__(self, o):
        res = self.__sub__(o)
        self._set_data(res._data)
        return self

    def __imul__(self, o):
        res = self.__mul__(o)
        self._set_data(res._data)
        return self

    def __itruediv__(self, o):
        res = self.__truediv__(o)
        self._set_data(res._data)
        return self

    def _compare(self, other, op):
        from ..ops.registry import apply_op
        if not isinstance(other, NDArray):
            other = NDArray(onp.asarray(other), ctx=self._ctx, dtype=self.dtype)
        # the registry tags the result _is_predicate (see _PREDICATE_OPS) so
        # np-style boolean indexing recognizes comparison results as masks
        return apply_op(op, self, other)

    def __eq__(self, o):
        return self._compare(o, "broadcast_equal")

    def __ne__(self, o):
        return self._compare(o, "broadcast_not_equal")

    def __gt__(self, o):
        return self._compare(o, "broadcast_greater")

    def __ge__(self, o):
        return self._compare(o, "broadcast_greater_equal")

    def __lt__(self, o):
        return self._compare(o, "broadcast_lesser")

    def __le__(self, o):
        return self._compare(o, "broadcast_lesser_equal")

    def __hash__(self):
        return id(self)

    # ------------------------------------------------------------------
    # method mirrors of common ops
    # ------------------------------------------------------------------
    def _op(self, name, **kw):
        from ..ops.registry import apply_op
        return apply_op(name, self, **kw)

    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        shape = kwargs.get("shape", shape)
        return self._op("reshape", shape=tuple(shape))

    def reshape_like(self, other):
        return self._op("reshape", shape=other.shape)

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        return self._op("transpose", axes=tuple(axes) if axes else None)

    def swapaxes(self, dim1, dim2):
        return self._op("swapaxes", dim1=dim1, dim2=dim2)

    def flatten(self):
        return self._op("flatten")

    def expand_dims(self, axis):
        return self._op("expand_dims", axis=axis)

    def squeeze(self, axis=None):
        return self._op("squeeze", axis=axis)

    def broadcast_to(self, shape):
        return self._op("broadcast_to", shape=tuple(shape))

    def broadcast_like(self, other):
        return self._op("broadcast_to", shape=other.shape)

    def sum(self, axis=None, keepdims=False):
        return self._op("sum", axis=_canon_axis(axis), keepdims=keepdims)

    def mean(self, axis=None, keepdims=False):
        return self._op("mean", axis=_canon_axis(axis), keepdims=keepdims)

    def max(self, axis=None, keepdims=False):
        return self._op("max", axis=_canon_axis(axis), keepdims=keepdims)

    def min(self, axis=None, keepdims=False):
        return self._op("min", axis=_canon_axis(axis), keepdims=keepdims)

    def prod(self, axis=None, keepdims=False):
        return self._op("prod", axis=_canon_axis(axis), keepdims=keepdims)

    def argmax(self, axis=None, keepdims=False):
        return self._op("argmax", axis=axis, keepdims=keepdims)

    def argmin(self, axis=None, keepdims=False):
        return self._op("argmin", axis=axis, keepdims=keepdims)

    def norm(self, ord=2, axis=None, keepdims=False):
        return self._op("norm", ord=ord, axis=_canon_axis(axis), keepdims=keepdims)

    def clip(self, a_min=None, a_max=None):
        return self._op("clip", a_min=a_min, a_max=a_max)

    def abs(self):
        return self._op("abs")

    def sqrt(self):
        return self._op("sqrt")

    def square(self):
        return self._op("square")

    def exp(self):
        return self._op("exp")

    def log(self):
        return self._op("log")

    def relu(self):
        return self._op("relu")

    def sigmoid(self):
        return self._op("sigmoid")

    def tanh(self):
        return self._op("tanh")

    def softmax(self, axis=-1):
        return self._op("softmax", axis=axis)

    def log_softmax(self, axis=-1):
        return self._op("log_softmax", axis=axis)

    def slice(self, begin, end, step=None):
        return self._op("slice", begin=tuple(begin), end=tuple(end),
                        step=tuple(step) if step else None)

    def slice_axis(self, axis, begin, end):
        return self._op("slice_axis", axis=axis, begin=begin, end=end)

    def take(self, indices, axis=0, mode="clip"):
        from ..ops.registry import apply_op
        return apply_op("take", self, indices, axis=axis, mode=mode)

    def one_hot(self, depth, on_value=1.0, off_value=0.0):
        return self._op("one_hot", depth=depth, on_value=on_value, off_value=off_value)

    def tile(self, reps):
        return self._op("tile", reps=tuple(reps) if isinstance(reps, (list, tuple)) else (reps,))

    def repeat(self, repeats, axis=None):
        return self._op("repeat", repeats=repeats, axis=axis)

    def flip(self, axis):
        return self._op("reverse", axis=axis)

    def zeros_like(self):
        return self._op("zeros_like")

    def ones_like(self):
        return self._op("ones_like")

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return self._op("split", num_outputs=num_outputs, axis=axis,
                        squeeze_axis=squeeze_axis)

    def dot(self, other):
        from ..ops.registry import apply_op
        return apply_op("dot", self, other)

    def tostype(self, stype):
        if stype == "default":
            return self
        from ..sparse import cast_storage
        return cast_storage(self, stype)

    # numpy-protocol interop
    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        """NEP-13 dispatch (parity: numpy_dispatch_protocol.py): numpy ufuncs
        applied to NDArrays run the device implementation from mx.np when one
        matches the call exactly; anything else (reduce/accumulate, dtype=,
        where=, out=, ufuncs with no device analog) computes on host via
        __array__ — defining __array_ufunc__ disables numpy's automatic
        coercion, so the fallback must be explicit or those calls TypeError."""
        from .. import numpy as mx_np
        fn = getattr(mx_np, ufunc.__name__, None)
        if method == "__call__" and fn is not None and not kwargs:
            try:
                return fn(*inputs)
            except Exception:  # noqa: BLE001 — fall through to host path
                pass
        import jax.numpy as jnp

        def unwrap(a):
            return a.asnumpy() if isinstance(a, NDArray) else a

        host_inputs = tuple(unwrap(a) for a in inputs)
        out = kwargs.pop("out", None)
        result = getattr(ufunc, method)(*host_inputs, **kwargs)
        if out is not None:
            outs = out if isinstance(out, tuple) else (out,)
            results = result if isinstance(result, tuple) else (result,)
            written = []
            for o, r in zip(outs, results):
                if isinstance(o, NDArray):
                    o._set_data(jnp.asarray(onp.asarray(r)).astype(
                        o.data.dtype))
                    written.append(o)
                else:
                    o[...] = r
                    written.append(o)
            return written[0] if len(written) == 1 else tuple(written)
        return result

    def __array_function__(self, func, types, args, kwargs):
        """NEP-18 dispatch: onp.mean(x)/onp.concatenate([...]) etc. route to
        the mx.np implementation when one exists."""
        from .. import numpy as mx_np
        fn = getattr(mx_np, func.__name__, None)
        if fn is None or fn is func:
            # no device implementation: evaluate on host via __array__
            def unwrap(a):
                if isinstance(a, NDArray):
                    return a.asnumpy()
                if isinstance(a, (list, tuple)):
                    return type(a)(unwrap(x) for x in a)
                return a
            return func(*[unwrap(a) for a in args], **kwargs)
        return fn(*args, **kwargs)

    def __dlpack__(self, **kw):
        return self._data.__dlpack__(**kw)

    def __dlpack_device__(self):
        return self._data.__dlpack_device__()

    def __repr__(self):
        return f"{self.asnumpy()!r}\n<NDArray {'x'.join(map(str, self.shape))} " \
               f"@{self._ctx} {self.dtype}>"

    def __str__(self):
        return str(self.asnumpy())

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]


def _single_device_of(arr):
    import jax
    if isinstance(arr, jax.core.Tracer):
        # a traced value lies on no device. Asking raises, and the error
        # composes a message that walks the whole trace so far: every op of
        # a traced model wraps its outputs here, and that walk was half of
        # a decode step's tracing time
        return None
    try:
        devs = arr.devices()
        if len(devs) == 1:
            return next(iter(devs))
    except Exception:
        pass
    return None


def _canon_axis(axis):
    if isinstance(axis, list):
        return tuple(axis)
    return axis


def _canon_index(key, raw=False):
    """Convert NDArray indices to jax-compatible; wrap scalars in tuple form."""
    def conv(k):
        if isinstance(k, NDArray):
            # legacy nd accepts float index arrays for gather (take
            # semantics); jnp requires integer indexers
            if k._data.dtype.kind == "f":
                return k._data.astype("int32")
            return k._data
        return k
    if isinstance(key, tuple):
        return tuple(conv(k) for k in key)
    if key is Ellipsis:
        return (Ellipsis,)
    return conv(key)


def _wrap_output(out, ctx):
    if isinstance(out, (list, tuple)):
        return tuple(NDArray(o, ctx=ctx) for o in out)
    return NDArray(out, ctx=ctx)


def array(source_array, ctx=None, dtype=None) -> NDArray:
    """Create an NDArray from any array-like (ndarray.py array() parity)."""
    return NDArray(source_array, ctx=ctx, dtype=dtype)
