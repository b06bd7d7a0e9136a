"""Testing utilities (parity: python/mxnet/test_utils.py — assert_almost_equal:561,
check_numeric_gradient:987, check_consistency:1428, rand_ndarray:388,
default_context, same)."""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

import numpy as onp

from .base import Context, MXNetError, current_context
from .ndarray.ndarray import NDArray

_DEFAULT_RTOL = {onp.dtype(onp.float16): 1e-2, onp.dtype(onp.float32): 1e-4,
                 onp.dtype(onp.float64): 1e-5}
_DEFAULT_ATOL = {onp.dtype(onp.float16): 1e-2, onp.dtype(onp.float32): 1e-5,
                 onp.dtype(onp.float64): 1e-8}


def default_context() -> Context:
    return current_context()


def set_default_context(ctx: Context):
    Context._default_ctx.stack = [ctx]


def _as_np(x):
    if isinstance(x, NDArray):
        return x.asnumpy()
    return onp.asarray(x)


def same(a, b):
    return onp.array_equal(_as_np(a), _as_np(b))


def almost_equal(a, b, rtol=None, atol=None, equal_nan=False):
    a, b = _as_np(a), _as_np(b)
    rtol = rtol or _DEFAULT_RTOL.get(a.dtype, 1e-5)
    atol = atol or _DEFAULT_ATOL.get(a.dtype, 1e-7)
    return onp.allclose(a.astype(onp.float64), b.astype(onp.float64), rtol, atol,
                        equal_nan=equal_nan)


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b"),
                        equal_nan=False):
    a_np, b_np = _as_np(a), _as_np(b)
    rtol = rtol if rtol is not None else _DEFAULT_RTOL.get(a_np.dtype, 1e-5)
    atol = atol if atol is not None else _DEFAULT_ATOL.get(a_np.dtype, 1e-7)
    if not onp.allclose(a_np.astype(onp.float64), b_np.astype(onp.float64),
                        rtol, atol, equal_nan=equal_nan):
        index = onp.unravel_index(
            onp.argmax(onp.abs(a_np.astype(onp.float64) - b_np)), a_np.shape) \
            if a_np.shape else ()
        diff = onp.abs(a_np.astype(onp.float64) - b_np).max()
        raise AssertionError(
            f"Items are not equal (rtol={rtol}, atol={atol}):\n max abs diff "
            f"{diff} at {index}\n {names[0]}: {a_np.ravel()[:8]}\n "
            f"{names[1]}: {b_np.ravel()[:8]}")


def rand_ndarray(shape, stype="default", density=None, dtype="float32", ctx=None,
                 scale=1.0):
    from . import ndarray as nd
    arr = nd.random.uniform(-scale, scale, shape=shape, ctx=ctx)
    return arr.astype(dtype)


def rand_shape_2d(dim0=10, dim1=10):
    return (onp.random.randint(1, dim0 + 1), onp.random.randint(1, dim1 + 1))


def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return (onp.random.randint(1, dim0 + 1), onp.random.randint(1, dim1 + 1),
            onp.random.randint(1, dim2 + 1))


def rand_shape_nd(num_dim, dim=10):
    return tuple(onp.random.randint(1, dim + 1, size=num_dim))


def check_numeric_gradient(fn, inputs: List[NDArray], grads=None, eps=1e-4,
                           rtol=1e-2, atol=1e-4):
    """Finite-difference gradient check (test_utils.py:987 pattern): `fn` maps
    NDArrays to a scalar NDArray; autograd gradients are compared to central
    differences."""
    from . import autograd

    for x in inputs:
        x.attach_grad()
    with autograd.record():
        y = fn(*inputs)
    y.backward()
    analytic = [x.grad.asnumpy().copy() for x in inputs]

    # Perturbations are built ON DEVICE (base + delta*onehot(i)) rather than
    # by mutating a host buffer and re-uploading: the on-device form needs
    # no H2D transfer per element at all.
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _perturbed(data, idx, delta):
        flat_d = data.reshape(-1)
        onehot = (jnp.arange(flat_d.shape[0]) == idx).astype(data.dtype)
        return (flat_d + onehot * delta).reshape(data.shape)

    for k, x in enumerate(inputs):
        base_dev = x.data
        num_grad = onp.zeros(x.shape, onp.float64)
        ng_flat = num_grad.ravel()
        for i in range(num_grad.size):
            x._set_data(_perturbed(base_dev, i, eps))
            f_pos = float(fn(*inputs).asscalar())
            x._set_data(_perturbed(base_dev, i, -eps))
            f_neg = float(fn(*inputs).asscalar())
            ng_flat[i] = (f_pos - f_neg) / (2 * eps)
        x._set_data(base_dev)
        assert_almost_equal(analytic[k], num_grad, rtol=rtol, atol=atol,
                            names=(f"analytic[{k}]", f"numeric[{k}]"))


def _to_jax(np_arr, like):
    import jax
    import jax.numpy as jnp
    return jax.device_put(jnp.asarray(np_arr, like.data.dtype),
                          like.context.jax_device())


def check_consistency(fn, inputs_np: List[onp.ndarray], ctx_list: List[Context],
                      dtypes=("float32",), rtol=None, atol=None, grad=False):
    """Cross-context/dtype oracle (test_utils.py:1428 pattern): run `fn` on every
    (ctx, dtype) pair and compare results against the first. With ``grad=True``
    also records the call, backwards it with all-ones head cotangents, and
    compares every input gradient across the pairs (the reference oracle
    compares forward AND backward across contexts)."""
    from . import autograd

    results = []
    for ctx in ctx_list:
        for dtype in dtypes:
            args = [NDArray(a, ctx=ctx, dtype=dtype) for a in inputs_np]
            if grad:
                for a in args:
                    a.attach_grad()
                with autograd.record():
                    out = fn(*args)
                    outs = list(out) if isinstance(out, (list, tuple)) else [out]
                autograd.backward(outs)
                row = [o.asnumpy().astype(onp.float64) for o in outs]
                row += [a.grad.asnumpy().astype(onp.float64) for a in args
                        if a.grad is not None]
            else:
                out = fn(*args)
                outs = out if isinstance(out, (list, tuple)) else [out]
                row = [o.asnumpy().astype(onp.float64) for o in outs]
            results.append(row)
    ref = results[0]
    for got in results[1:]:
        for r, g in zip(ref, got):
            assert_almost_equal(r, g, rtol=rtol or 1e-3, atol=atol or 1e-4)
    return results


def list_gpus():
    from .base import num_gpus
    return list(range(num_gpus()))


def gpu_device(device_id=0):
    from .base import gpu, num_gpus
    if num_gpus() > device_id:
        return gpu(device_id)
    return None


def environment(name, value):
    """Scoped env var override (test_utils.py environment)."""
    import os
    from contextlib import contextmanager

    @contextmanager
    def _scope():
        old = os.environ.get(name)
        if value is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = str(value)
        try:
            yield
        finally:
            if old is None:
                os.environ.pop(name, None)
            else:
                os.environ[name] = old
    return _scope()


def get_shapes_detection(num_images, size=96, max_objects=3, num_classes=3,
                         seed=0, min_frac=4):
    """Synthetic detection dataset: solid geometric shapes on a noise
    background (the SSD accuracy-evidence set; reference analogue:
    example/ssd's train/evaluate pipeline run on a small real set).

    Classes are distinguished by geometry alone (color is random):
    0 = filled square, 1 = disc, 2 = cross. Returns

        images : (N, 3, size, size) float32 in [0, 1]
        labels : (N, max_objects, 5) float32 rows [cls, x1, y1, x2, y2]
                 (corner format, normalized to [0, 1]; -1 rows are padding)

    Placements are rejection-sampled so boxes barely overlap (IoU <= 0.2):
    every labeled object stays visible, so the ground truth is exact and a
    perfect detector can reach mAP ~1.0.
    """
    rng = onp.random.RandomState(seed)
    imgs = onp.empty((num_images, 3, size, size), onp.float32)
    labels = -onp.ones((num_images, max_objects, 5), onp.float32)

    def _iou(a, b):
        ix = max(0, min(a[2], b[2]) - max(a[0], b[0]))
        iy = max(0, min(a[3], b[3]) - max(a[1], b[1]))
        inter = ix * iy
        ua = ((a[2] - a[0]) * (a[3] - a[1])
              + (b[2] - b[0]) * (b[3] - b[1]) - inter)
        return inter / max(ua, 1)

    for i in range(num_images):
        img = rng.uniform(0.0, 0.25, (3, size, size)).astype(onp.float32)
        placed = []
        j = 0
        for _ in range(rng.randint(1, max_objects + 1)):
            cls = rng.randint(num_classes)
            for _try in range(20):
                s = rng.randint(size // min_frac, size // 2)
                x1 = rng.randint(0, size - s)
                y1 = rng.randint(0, size - s)
                box = (x1, y1, x1 + s, y1 + s)
                if all(_iou(box, p) <= 0.2 for p in placed):
                    break
            else:
                continue
            placed.append(box)
            color = rng.uniform(0.6, 1.0, 3).astype(onp.float32)
            yy, xx = onp.mgrid[0:s, 0:s]
            c = (s - 1) / 2.0
            if cls == 0:
                mask = onp.ones((s, s), bool)
            elif cls == 1:
                mask = (yy - c) ** 2 + (xx - c) ** 2 <= (s / 2.0) ** 2
            else:
                t = max(s // 4, 1)
                mask = (onp.abs(xx - c) <= t / 2.0) | (onp.abs(yy - c) <= t / 2.0)
            region = img[:, y1:y1 + s, x1:x1 + s]
            img[:, y1:y1 + s, x1:x1 + s] = onp.where(
                mask[None], color[:, None, None], region)
            labels[i, j] = [cls, x1 / size, y1 / size,
                            (x1 + s) / size, (y1 + s) / size]
            j += 1
        imgs[i] = img
    return imgs, labels
