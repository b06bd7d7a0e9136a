"""Neural-network operators.

Parity surface: src/operator/nn/ (convolution, fully_connected, pooling, batch_norm,
layer_norm, group_norm, dropout, softmax-inl.h w/ fp32-accum dtype override:629-733,
activation), src/operator/rnn-inl.h (monolithic RNN op), and the fork's fused
attention ops src/operator/contrib/transformer.cc:650-828.

TPU-native design: convolution/matmul map straight onto the MXU via
lax.conv_general_dilated / dot_general; normalisations are fused by XLA; the RNN op
is a lax.scan (compiled once, no per-step dispatch — the cuDNN-fused-RNN analog).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry as _telemetry
from .registry import register


def _tup(v, n):
    if v is None:
        return (1,) * n
    if isinstance(v, int):
        return (v,) * n
    t = tuple(v)
    return t if len(t) == n else t * n


# ---------------------------------------------------------------------------
# FullyConnected (nn/fully_connected.cc:254-344)
# ---------------------------------------------------------------------------
@register("FullyConnected", jit=True)
def fully_connected(x, weight, bias=None, *, num_hidden=0, no_bias=False, flatten=True):
    """y = x W^T + b. weight is (num_hidden, in_units) like the reference."""
    if flatten and x.ndim > 2:
        x = x.reshape(x.shape[0], -1)
    y = jnp.matmul(x, weight.T)
    if bias is not None and not no_bias:
        y = y + bias
    return y


# ---------------------------------------------------------------------------
# Convolution / Deconvolution (nn/convolution.cc) — NCHW/OIHW like the reference
# ---------------------------------------------------------------------------
_CONV_DN = {1: ("NCH", "OIH", "NCH"), 2: ("NCHW", "OIHW", "NCHW"),
            3: ("NCDHW", "OIDHW", "NCDHW")}


@register("Convolution", jit=True)
def convolution(x, weight, bias=None, *, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=0, num_group=1, no_bias=False, layout=None):
    nd = x.ndim - 2
    stride = _tup(stride, nd)
    dilate = _tup(dilate, nd)
    pad = _tup(pad if pad is not None else 0, nd)
    dn = lax.conv_dimension_numbers(x.shape, weight.shape, _CONV_DN[nd])
    # no preferred_element_type: the TPU MXU already accumulates bf16 convs in
    # fp32, and requesting fp32 output breaks lax's conv transpose (grad) rule
    y = lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=dn, feature_group_count=num_group)
    if bias is not None and not no_bias:
        y = y + bias.reshape((1, -1) + (1,) * nd)
    # residual-save tag: under the train step's remat policy (MXNET_TRAIN_REMAT
    # =conv, parallel/train_step.py) only conv outputs are saved for backward;
    # the BN/ReLU elementwise chain is recomputed instead of round-tripping
    # HBM. A no-op outside jax.checkpoint.
    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(y, "conv_out")


@register("Deconvolution", jit=True)
def deconvolution(x, weight, bias=None, *, kernel=None, stride=None, dilate=None,
                  pad=None, adj=None, num_filter=0, num_group=1, no_bias=False,
                  target_shape=None, layout=None):
    """Transposed convolution. weight layout (in_c, out_c/groups, *k) as reference."""
    nd = x.ndim - 2
    stride = _tup(stride, nd)
    dilate = _tup(dilate, nd)
    pad = _tup(pad if pad is not None else 0, nd)
    adj = _tup(adj if adj is not None else 0, nd)
    k = weight.shape[2:]
    # conv_transpose via gradient-of-conv: use lax.conv_transpose with IOHW spec
    dn = _CONV_DN[nd]
    pads = []
    for i in range(nd):
        eff_k = (k[i] - 1) * dilate[i] + 1
        pads.append((eff_k - 1 - pad[i], eff_k - 1 - pad[i] + adj[i]))
    if num_group == 1:
        y = lax.conv_transpose(
            x, weight, strides=stride, padding=pads, rhs_dilation=dilate,
            dimension_numbers=(dn[0], dn[1].replace("O", "X").replace("I", "O")
                               .replace("X", "I"), dn[2]),
            transpose_kernel=True)
    else:
        xs = jnp.split(x, num_group, axis=1)
        ws = jnp.split(weight, num_group, axis=0)
        y = jnp.concatenate([
            lax.conv_transpose(xi, wi, strides=stride, padding=pads,
                               rhs_dilation=dilate,
                               dimension_numbers=(dn[0],
                                                  dn[1].replace("O", "X").replace("I", "O").replace("X", "I"),
                                                  dn[2]),
                               transpose_kernel=True)
            for xi, wi in zip(xs, ws)], axis=1)
    if bias is not None and not no_bias:
        y = y + bias.reshape((1, -1) + (1,) * nd)
    return y


# ---------------------------------------------------------------------------
# Pooling (nn/pooling.cc)
# ---------------------------------------------------------------------------
@register("Pooling", jit=True)
def pooling(x, *, kernel=None, pool_type="max", global_pool=False, stride=None,
            pad=None, pooling_convention="valid", count_include_pad=True, cudnn_off=False,
            layout=None):
    nd = x.ndim - 2
    if global_pool:
        axes = tuple(range(2, x.ndim))
        if pool_type == "max":
            return jnp.max(x, axis=axes, keepdims=True)
        if pool_type in ("avg", "sum"):
            r = jnp.sum(x, axis=axes, keepdims=True)
            if pool_type == "avg":
                r = r / math.prod(x.shape[2:])
            return r
        raise ValueError(pool_type)
    kernel = _tup(kernel, nd)
    stride = _tup(stride if stride is not None else kernel, nd)
    pad = _tup(pad if pad is not None else 0, nd)
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    if pooling_convention == "full":
        # ceil-mode output: pad on the high side so ceil-division sizes result
        pads = [(0, 0), (0, 0)]
        for i in range(nd):
            in_sz = x.shape[2 + i]
            out_sz = int(math.ceil((in_sz + 2 * pad[i] - kernel[i]) / stride[i])) + 1
            needed = (out_sz - 1) * stride[i] + kernel[i] - in_sz - pad[i]
            pads.append((pad[i], max(needed, pad[i])))
    else:
        pads = [(0, 0), (0, 0)] + [(p, p) for p in pad]
    # NB: init must be a weak-typed Python scalar — an array init stops XLA/JAX
    # from matching the differentiable reduce_window_max/add primitives
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
            else int(jnp.iinfo(x.dtype).min)
        return lax.reduce_window(x, init, lax.max, window, strides, pads)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(x, 0.0 if jnp.issubdtype(x.dtype, jnp.floating)
                              else 0, lax.add, window, strides, pads)
        if pool_type == "sum":
            return s
        if count_include_pad:
            return s / math.prod(kernel)
        ones = jnp.ones_like(x)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        return s / cnt
    if pool_type == "lp":
        s = lax.reduce_window(jnp.abs(x) ** 2, 0.0, lax.add, window, strides, pads)
        return jnp.sqrt(s)
    raise ValueError(pool_type)


@register("UpSampling", jit=True)
def upsampling(x, *, scale=2, sample_type="nearest", num_args=1):
    n, c, h, w = x.shape
    if sample_type == "nearest":
        return jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
    return jax.image.resize(x, (n, c, h * scale, w * scale), method="bilinear")


@register("BilinearResize2D", jit=True)
def bilinear_resize_2d(x, *, height=0, width=0, scale_height=None, scale_width=None,
                       mode="size"):
    n, c, h, w = x.shape
    if scale_height is not None:
        height = int(h * scale_height)
        width = int(w * scale_width)
    return jax.image.resize(x, (n, c, height, width), method="bilinear")


# ---------------------------------------------------------------------------
# Activation (nn/activation.cc)
# ---------------------------------------------------------------------------
@register("Activation")
def activation(x, *, act_type="relu"):
    acts = {"relu": lambda v: jnp.maximum(v, 0), "sigmoid": jax.nn.sigmoid,
            "tanh": jnp.tanh, "softrelu": jax.nn.softplus,
            "softsign": lambda v: v / (1 + jnp.abs(v)), "log_sigmoid": jax.nn.log_sigmoid,
            "mish": lambda v: v * jnp.tanh(jax.nn.softplus(v)),
            "gelu": lambda v: jax.nn.gelu(v, approximate=False),
            "silu": jax.nn.silu}
    return acts[act_type](x)


# ---------------------------------------------------------------------------
# softmax family (nn/softmax-inl.h; fp32 accumulation for bf16 inputs, :629-733)
# ---------------------------------------------------------------------------
def _softmax_core(x, axis, temperature, length, log: bool):
    acc = jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else x.dtype
    xa = x.astype(acc)
    if temperature is not None and temperature != 1.0:
        xa = xa / temperature
    if length is not None:
        pos = jnp.arange(x.shape[axis])
        shape = [1] * x.ndim
        shape[axis] = x.shape[axis]
        bshape = [1] * x.ndim
        bshape[0] = x.shape[0]
        mask = pos.reshape(shape) < length.astype(jnp.int32).reshape(bshape)
        xa = jnp.where(mask, xa, -jnp.inf)
        out = jax.nn.log_softmax(xa, axis=axis) if log else jax.nn.softmax(xa, axis=axis)
        out = jnp.where(mask, out, 0.0)
    else:
        out = jax.nn.log_softmax(xa, axis=axis) if log else jax.nn.softmax(xa, axis=axis)
    return out.astype(x.dtype)


@register("softmax")
def softmax(x, length=None, *, axis=-1, temperature=None, use_length=False, dtype=None):
    return _softmax_core(x, axis, temperature, length if use_length else None, log=False)


@register("log_softmax")
def log_softmax(x, length=None, *, axis=-1, temperature=None, use_length=False, dtype=None):
    return _softmax_core(x, axis, temperature, length if use_length else None, log=True)


@register("masked_softmax")
def masked_softmax(x, mask, *, axis=-1, temperature=1.0, normalize=True):
    acc = jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else x.dtype
    xa = x.astype(acc) / temperature
    xa = jnp.where(mask.astype(bool), xa, -jnp.inf)
    out = jax.nn.softmax(xa, axis=axis)
    out = jnp.where(mask.astype(bool), out, 0.0)
    return out.astype(x.dtype)


@register("softmin")
def softmin(x, *, axis=-1, temperature=None, dtype=None):
    return _softmax_core(-x, axis, temperature, None, log=False)


@register("SoftmaxActivation")
def softmax_activation(x, *, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(x, axis=1)
    return jax.nn.softmax(x.reshape(x.shape[0], -1), axis=-1).reshape(x.shape)


@register("SoftmaxOutput")
def softmax_output(x, label, *, grad_scale=1.0, ignore_label=-1.0, multi_output=False,
                   use_ignore=False, preserve_shape=False, normalization="null",
                   out_grad=False, smooth_alpha=0.0):
    """Legacy softmax+CE-gradient op (src/operator/softmax_output.cc). Forward is
    softmax; gradient w.r.t. x is (p - onehot(label)) * grad_scale."""
    axis = 1 if multi_output else -1

    @jax.custom_vjp
    def f(xx, ll):
        return jax.nn.softmax(xx.astype(jnp.float32), axis=axis).astype(xx.dtype)

    def f_fwd(xx, ll):
        p = jax.nn.softmax(xx.astype(jnp.float32), axis=axis)
        return p.astype(xx.dtype), (p, ll)

    def f_bwd(res, g):
        p, ll = res
        depth = p.shape[axis]
        oh = jax.nn.one_hot(ll.astype(jnp.int32), depth, axis=axis, dtype=p.dtype)
        if smooth_alpha:
            oh = oh * (1 - smooth_alpha) + smooth_alpha / depth
        dx = (p - oh)
        if use_ignore:
            keep = (ll != ignore_label).astype(p.dtype)
            keep = jnp.expand_dims(keep, axis) if keep.ndim < p.ndim else keep
            dx = dx * keep
        scale = grad_scale
        if normalization == "valid" and use_ignore:
            valid = jnp.maximum(jnp.sum(ll != ignore_label).astype(p.dtype), 1.0)
            scale = scale / valid
        elif normalization == "batch":
            scale = scale / p.shape[0]
        return (dx * scale).astype(p.dtype), None

    f.defvjp(f_fwd, f_bwd)
    return f(x, label)


@register("softmax_cross_entropy")
def softmax_cross_entropy(data, label):
    logp = jax.nn.log_softmax(data.astype(jnp.float32), axis=-1)
    oh = jax.nn.one_hot(label.astype(jnp.int32), data.shape[-1], dtype=logp.dtype)
    return -jnp.sum(oh * logp)


# ---------------------------------------------------------------------------
# normalisation (nn/batch_norm.cc, layer_norm.cc, group_norm.cc, instance_norm.cc,
# l2_normalization.cc, lrn.cc)
# ---------------------------------------------------------------------------
def _bn_onepass_enabled(dtype) -> bool:
    """Resolve MXNET_BN_ONEPASS for this input dtype. 'auto' (default) keeps
    the one-pass E[x^2]-mu^2 moments for sub-f32 inputs only: a bf16/f16
    activation cannot carry the |mean|/std ratio that makes the subtraction
    cancel at f32 accumulation, while f32/f64 inputs can (mean~300/std~0.01
    clamps var to 0) and therefore get the two-pass reference form."""
    from .. import config as _config
    v = _config.get("MXNET_BN_ONEPASS")
    if isinstance(v, bool):               # config.set(..., True/False)
        return v
    s = str(v).strip().lower()
    if s in ("auto", ""):
        return dtype in (jnp.bfloat16, jnp.float16)
    return s in ("1", "true", "yes", "on")


@register("BatchNorm", jit=True)
def batch_norm(x, gamma, beta, moving_mean, moving_var, *, eps=1e-5, momentum=0.9,
               fix_gamma=True, use_global_stats=False, output_mean_var=False, axis=1,
               cudnn_off=False, training=False, axis_name=None):
    """BatchNorm (nn/batch_norm.cc). Returns (out, new_moving_mean, new_moving_var);
    stat write-back is handled by the caller (gluon layer / nd wrapper) — the
    functional formulation of the reference's in-op aux-state mutation.

    ``axis_name``: when set and tracing inside shard_map/pmap, batch moments
    are averaged across that mesh axis (lax.pmean) — the SyncBatchNorm hook."""
    acc = jnp.float32
    from .. import config as _config
    # bf16 fast path: every tensor that touches HBM (x, out, cotangents at
    # the conv boundaries) stays bf16; all arithmetic happens on in-register
    # f32 upcasts (moment accumulation, the a/b scale/shift, and therefore
    # the dgamma/dbeta gradient reductions) — cuDNN's fp16-AMP BatchNorm
    # semantics. Inherently one-pass. Measured 2204->2660 img/s on ResNet-50
    # b128 v5e (PERF.md round 5).
    bf16_fast = (x.dtype == jnp.bfloat16 and
                 _config.get("MXNET_BN_BF16_REDUCE"))
    red = tuple(i for i in range(x.ndim) if i != axis)
    bshape = [1] * x.ndim
    bshape[axis] = x.shape[axis]
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    # xa32 is an IN-REGISTER upcast: XLA fuses the convert into whatever
    # reads x, so no f32 copy of the activation ever hits HBM — but squares
    # and sums accumulate at f32 precision (E[x^2]-mu^2 would be hopeless
    # with bf16-rounded squares)
    xa32 = x.astype(acc)
    if training and not use_global_stats:
        mean = jnp.mean(xa32, axis=red)
        onepass = bf16_fast or _bn_onepass_enabled(x.dtype)
        if axis_name is not None:
            # cross-device moments via E[x^2] - E[x]^2 (one pmean pair) —
            # the SyncBatchNorm hook
            sq = lax.pmean(jnp.mean(jnp.square(xa32), axis=red), axis_name)
            mean = lax.pmean(mean, axis_name)
            var = jnp.maximum(sq - jnp.square(mean), 0.0)
        elif onepass:
            sq = jnp.mean(jnp.square(xa32), axis=red)
            var = jnp.maximum(sq - jnp.square(mean), 0.0)
        else:
            var = jnp.mean(jnp.square(xa32 - mean.reshape(bshape)), axis=red)
        new_mean = momentum * moving_mean.astype(acc) + (1 - momentum) * mean
        new_var = momentum * moving_var.astype(acc) + (1 - momentum) * var
    else:
        mean = moving_mean.astype(acc)
        var = moving_var.astype(acc)
        new_mean, new_var = mean, var
    inv = lax.rsqrt(var + eps)
    if bf16_fast:
        a = inv * gamma.astype(acc)
        b = beta.astype(acc) - mean * a
        out = x * a.reshape(bshape) + b.reshape(bshape)
    else:
        # the (x - mu) form is numerically preferable in f32 (no x*a vs mu*a
        # cancellation), and here the f32 intermediate is the intent
        out = (xa32 - mean.reshape(bshape)) * \
            (inv * gamma.astype(acc)).reshape(bshape) \
            + beta.astype(acc).reshape(bshape)
    return (out.astype(x.dtype), new_mean.astype(moving_mean.dtype),
            new_var.astype(moving_var.dtype))


@register("LayerNorm", jit=True)
def layer_norm(x, gamma, beta, *, axis=-1, eps=1e-5, output_mean_var=False):
    acc = jnp.float32
    from .. import config as _config
    xa = x.astype(acc)   # in-register upcast; fused into whatever reads x
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    if x.dtype == jnp.bfloat16 and _config.get("MXNET_BN_BF16_REDUCE"):
        # same recipe as BatchNorm's bf16 fast path: one-pass f32 moments,
        # f32 scale/shift in-register, every materialized tensor bf16
        mean = jnp.mean(xa, axis=axis, keepdims=True)
        sq = jnp.mean(jnp.square(xa), axis=axis, keepdims=True)
        var = jnp.maximum(sq - jnp.square(mean), 0.0)
        inv = lax.rsqrt(var + eps)
        a = inv * gamma.astype(acc).reshape(shape)
        b = beta.astype(acc).reshape(shape) - mean * a
        out = (x * a + b).astype(x.dtype)
    else:
        mean = jnp.mean(xa, axis=axis, keepdims=True)
        var = jnp.mean(jnp.square(xa - mean), axis=axis, keepdims=True)
        inv = lax.rsqrt(var + eps)
        out = ((xa - mean) * inv * gamma.astype(acc).reshape(shape)
               + beta.astype(acc).reshape(shape)).astype(x.dtype)
    if output_mean_var:
        return out, jnp.squeeze(mean, axis), jnp.squeeze(var, axis)
    return out


@register("RMSNorm", jit=True)
def rms_norm(x, gamma, *, axis=-1, eps=1e-6):
    acc = jnp.float32
    xa = x.astype(acc)
    ms = jnp.mean(jnp.square(xa), axis=axis, keepdims=True)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    return (xa * lax.rsqrt(ms + eps) * gamma.astype(acc).reshape(shape)).astype(x.dtype)


@register("GroupNorm", jit=True)
def group_norm(x, gamma, beta, *, num_groups=1, eps=1e-5, output_mean_var=False):
    n, c = x.shape[:2]
    g = num_groups
    acc = jnp.float32
    xa = x.astype(acc).reshape((n, g, c // g) + x.shape[2:])
    red = tuple(range(2, xa.ndim))
    mean = jnp.mean(xa, axis=red, keepdims=True)
    var = jnp.mean(jnp.square(xa - mean), axis=red, keepdims=True)
    out = (xa - mean) * lax.rsqrt(var + eps)
    out = out.reshape(x.shape)
    shape = (1, c) + (1,) * (x.ndim - 2)
    out = out * gamma.astype(acc).reshape(shape) + beta.astype(acc).reshape(shape)
    return out.astype(x.dtype)


@register("InstanceNorm", jit=True)
def instance_norm(x, gamma, beta, *, eps=1e-3):
    acc = jnp.float32
    xa = x.astype(acc)
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(xa, axis=red, keepdims=True)
    var = jnp.mean(jnp.square(xa - mean), axis=red, keepdims=True)
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    out = (xa - mean) * lax.rsqrt(var + eps) * gamma.astype(acc).reshape(shape) \
        + beta.astype(acc).reshape(shape)
    return out.astype(x.dtype)


@register("L2Normalization", jit=True)
def l2_normalization(x, *, eps=1e-10, mode="instance"):
    if mode == "instance":
        norm = jnp.sqrt(jnp.sum(jnp.square(x).reshape(x.shape[0], -1), axis=1) + eps)
        return x / norm.reshape((-1,) + (1,) * (x.ndim - 1))
    if mode == "channel":
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=1, keepdims=True) + eps)
        return x / norm
    if mode == "spatial":
        norm = jnp.sqrt(jnp.sum(jnp.square(x).reshape(x.shape[0], x.shape[1], -1),
                                axis=2) + eps)
        return x / norm.reshape(x.shape[:2] + (1,) * (x.ndim - 2))
    raise ValueError(mode)


@register("LRN", jit=True)
def lrn(x, *, nsize=5, alpha=1e-4, beta=0.75, knorm=2.0):
    sq = jnp.square(x)
    half = nsize // 2
    padded = jnp.pad(sq, [(0, 0), (half, half)] + [(0, 0)] * (x.ndim - 2))
    acc = sum(padded[:, i:i + x.shape[1]] for i in range(nsize))
    return x / jnp.power(knorm + alpha / nsize * acc, beta)


# ---------------------------------------------------------------------------
# Dropout (nn/dropout.cc) — key passed explicitly; wrappers thread the global RNG
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("keep", "local", "mesh", "axes"))
def _bits_per_shard(key, *, keep, local, mesh, axes):
    """A keep mask whose dimension 0 is divided over ``axes`` of ``mesh``,
    each shard's ``local`` rows drawn on that shard from its own fold of
    ``key``; mesh axes not named stay the partitioner's. Jitted so that a
    model's many sites of one shape are traced and lowered once."""
    from jax.sharding import PartitionSpec as P

    def draw(k):
        return jax.random.bernoulli(
            jax.random.fold_in(k, lax.axis_index(axes)), keep, local)

    return jax.shard_map(draw, mesh=mesh, in_specs=P(), out_specs=P(axes),
                         axis_names=frozenset(axes))(key)


def _dropout_bits(key, keep, shape):
    """The keep mask of ``shape``: one draw, or one draw a shard of the batch.

    A data-parallel train step publishes the mesh axes its batch is divided
    over (``parallel.mesh.batch_axes``). The SPMD partitioner cannot divide a
    generator's draw, so a mask drawn whole there is drawn whole on every
    device, each of which uses its own 1/n. Under such a step the mask is
    drawn a shard at a time instead, unless its dimension 0 is 1 (broadcast
    over the batch) or the devices do not divide it."""
    from ..parallel.mesh import current_batch_axes
    pub = current_batch_axes()
    if pub is None:
        return jax.random.bernoulli(key, keep, shape)
    n = pub.size
    if not shape or shape[0] == 1 or shape[0] % n:
        pub.on_draw("whole")
        return jax.random.bernoulli(key, keep, shape)
    pub.on_draw("per_shard")
    return _bits_per_shard(key, keep=keep, local=(shape[0] // n,) + shape[1:],
                           mesh=pub.mesh.mesh, axes=pub.axes)


@register("Dropout")
def dropout(x, key=None, *, p=0.5, mode="training", axes=(), training=False,
            cudnn_off=False):
    """Inverted dropout: ``x * mask / (1 - p)``, the mask's dimensions in
    ``axes`` broadcast.

    The same key gives the same mask, forward, backward and on a retried or
    replayed step. Under a ``ParallelTrainStep`` whose batch is divided over
    n > 1 devices the mask is drawn a shard at a time (``_dropout_bits``),
    so there the same key gives another mask on another mesh size, as
    ``step`` and ``step_n`` already differ. A mask is independent bits, and
    any partition of it is a mask: a model whose dimension 0 is not the batch
    (time-major) gets as valid a one, which the partitioner reshards."""
    if not training or p <= 0 or key is None:
        return x
    shape = list(x.shape)
    for a in axes:
        shape[a] = 1
    keep = 1.0 - p
    mask = _dropout_bits(key, keep, tuple(shape)).astype(x.dtype) / keep
    return x * mask


# ---------------------------------------------------------------------------
# Embedding (tensor/indexing_op.cc Embedding)
# ---------------------------------------------------------------------------
@register("Embedding", jit=True)
def embedding(indices, weight, *, input_dim=0, output_dim=0, dtype="float32",
              sparse_grad=False):
    idx = indices.astype(jnp.int32)
    return jnp.take(weight, idx, axis=0)


# ---------------------------------------------------------------------------
# RNN — monolithic fused op (rnn-inl.h:419-1528). lax.scan == the cuDNN fused path.
# ---------------------------------------------------------------------------
def _gru_step(gates_x, gates_h, h_prev):
    rx, zx, nx = jnp.split(gates_x, 3, axis=-1)
    rh, zh, nh = jnp.split(gates_h, 3, axis=-1)
    r = jax.nn.sigmoid(rx + rh)
    z = jax.nn.sigmoid(zx + zh)
    n = jnp.tanh(nx + r * nh)
    return (1 - z) * n + z * h_prev


def _single_layer_rnn(mode, x, h0, c0, wx, wh, bx, bh, reverse=False):
    """x: (T, N, I); returns (T, N, H), hT, cT."""
    if reverse:
        x = jnp.flip(x, axis=0)
    gx_all = jnp.einsum("tni,gi->tng", x, wx) + bx  # (T, N, G*H)

    def step(carry, gx):
        h_prev, c_prev = carry
        gh = jnp.matmul(h_prev, wh.T) + bh
        if mode == "lstm":
            i, f, g, o = jnp.split(gx + gh, 4, axis=-1)
            i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
            c = f * c_prev + i * jnp.tanh(g)
            h = o * jnp.tanh(c)
            return (h, c), h
        if mode == "gru":
            h = _gru_step(gx, gh, h_prev)
            return (h, c_prev), h
        h = jnp.tanh(gx + gh) if mode == "rnn_tanh" else jnp.maximum(gx + gh, 0)
        return (h, c_prev), h

    (hT, cT), ys = lax.scan(step, (h0, c0), gx_all)
    if reverse:
        ys = jnp.flip(ys, axis=0)
    return ys, hT, cT


def _num_gates(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]


def rnn_unpack_params(params, mode, num_layers, input_size, hidden, bidirectional):
    """Unpack the reference's flat param vector layout (rnn-inl.h: all wx/wh then
    all bx/bh, layer-major, direction-minor)."""
    g = _num_gates(mode)
    d = 2 if bidirectional else 1
    offset = 0
    weights = []
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else hidden * d
        for _ in range(d):
            wx = lax.dynamic_slice(params, (offset,), (g * hidden * in_sz,)).reshape(
                g * hidden, in_sz)
            offset += g * hidden * in_sz
            wh = lax.dynamic_slice(params, (offset,), (g * hidden * hidden,)).reshape(
                g * hidden, hidden)
            offset += g * hidden * hidden
            weights.append((wx, wh))
    biases = []
    for layer in range(num_layers):
        for _ in range(d):
            bx = lax.dynamic_slice(params, (offset,), (g * hidden,))
            offset += g * hidden
            bh = lax.dynamic_slice(params, (offset,), (g * hidden,))
            offset += g * hidden
            biases.append((bx, bh))
    return [(wx, wh, bx, bh) for (wx, wh), (bx, bh) in zip(weights, biases)]


def rnn_param_size(mode, num_layers, input_size, hidden, bidirectional):
    g = _num_gates(mode)
    d = 2 if bidirectional else 1
    size = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else hidden * d
        size += d * (g * hidden * in_sz + g * hidden * hidden + 2 * g * hidden)
    return size


@register("RNN", jit=True)
def rnn(x, params, state, state_cell=None, *, state_size=0, num_layers=1,
        bidirectional=False, mode="lstm", p=0.0, state_outputs=True,
        projection_size=None, use_sequence_length=False, lstm_state_clip_min=None,
        lstm_state_clip_max=None):
    """Monolithic RNN op (rnn-inl.h:419): x (T,N,I), flat params, state (L*D,N,H).
    Entire multilayer bidirectional net compiles to nested lax.scans — the TPU
    analog of the cuDNN fused RNN path (rnn.cu:47)."""
    T, N, I = x.shape
    H = state_size
    d = 2 if bidirectional else 1
    layers = rnn_unpack_params(params, mode, num_layers, I, H, bidirectional)
    hs, cs = [], []
    inp = x
    for layer in range(num_layers):
        outs = []
        for direction in range(d):
            li = layer * d + direction
            wx, wh, bx, bh = layers[li]
            h0 = state[li]
            c0 = state_cell[li] if (mode == "lstm" and state_cell is not None) \
                else jnp.zeros_like(h0)
            ys, hT, cT = _single_layer_rnn(mode, inp, h0, c0, wx, wh, bx, bh,
                                           reverse=(direction == 1))
            outs.append(ys)
            hs.append(hT)
            cs.append(cT)
        inp = jnp.concatenate(outs, axis=-1) if d == 2 else outs[0]
    out = inp
    hT = jnp.stack(hs, axis=0)
    if mode == "lstm":
        cT = jnp.stack(cs, axis=0)
        return out, hT, cT
    return out, hT


# ---------------------------------------------------------------------------
# fused attention (contrib/transformer.cc:650-828 — the fork's headline ops)
# ---------------------------------------------------------------------------
@register("_contrib_interleaved_matmul_selfatt_qk", jit=True)
def interleaved_matmul_selfatt_qk(qkv, *, heads):
    """qkv: (L, N, 3*H*D) interleaved per head. Returns (N*heads, L, L) scaled QK^T
    (transformer.cc:650)."""
    L, N, _ = qkv.shape
    D = qkv.shape[2] // (3 * heads)
    q, k, _v = _deinterleave_qkv(qkv, heads, D)
    scale = 1.0 / math.sqrt(D)
    att = jnp.einsum("nhld,nhmd->nhlm", q * scale, k,
                     preferred_element_type=jnp.float32).astype(qkv.dtype)
    return att.reshape(N * heads, L, L)


def _deinterleave_qkv(qkv, heads, D):
    L, N, _ = qkv.shape
    x = qkv.reshape(L, N, heads, 3, D)
    q = x[:, :, :, 0].transpose(1, 2, 0, 3)  # (N, h, L, D)
    k = x[:, :, :, 1].transpose(1, 2, 0, 3)
    v = x[:, :, :, 2].transpose(1, 2, 0, 3)
    return q, k, v


@register("_contrib_interleaved_matmul_selfatt_valatt", jit=True)
def interleaved_matmul_selfatt_valatt(qkv, att, *, heads):
    """att: (N*heads, L, L) softmaxed; returns (L, N, H*D) (transformer.cc:691)."""
    L, N, _ = qkv.shape
    D = qkv.shape[2] // (3 * heads)
    _q, _k, v = _deinterleave_qkv(qkv, heads, D)
    a = att.reshape(N, heads, L, L)
    out = jnp.einsum("nhlm,nhmd->nhld", a, v,
                     preferred_element_type=jnp.float32).astype(qkv.dtype)
    return out.transpose(2, 0, 1, 3).reshape(L, N, heads * D)


@register("_contrib_interleaved_matmul_encdec_qk", jit=True)
def interleaved_matmul_encdec_qk(q, kv, *, heads):
    Lq, N, HD = q.shape
    D = HD // heads
    qh = q.reshape(Lq, N, heads, D).transpose(1, 2, 0, 3)
    Lk = kv.shape[0]
    x = kv.reshape(Lk, N, heads, 2, D)
    kh = x[:, :, :, 0].transpose(1, 2, 0, 3)
    scale = 1.0 / math.sqrt(D)
    att = jnp.einsum("nhld,nhmd->nhlm", qh * scale, kh,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return att.reshape(N * heads, Lq, Lk)


@register("_contrib_interleaved_matmul_encdec_valatt", jit=True)
def interleaved_matmul_encdec_valatt(kv, att, *, heads):
    Lk, N, HD2 = kv.shape
    D = HD2 // (2 * heads)
    x = kv.reshape(Lk, N, heads, 2, D)
    vh = x[:, :, :, 1].transpose(1, 2, 0, 3)
    Lq = att.shape[1]
    a = att.reshape(N, heads, Lq, Lk)
    out = jnp.einsum("nhlm,nhmd->nhld", a, vh,
                     preferred_element_type=jnp.float32).astype(kv.dtype)
    return out.transpose(2, 0, 1, 3).reshape(Lq, N, heads * D)


@register("_contrib_div_sqrt_dim")
def div_sqrt_dim(x):
    """x / sqrt(last_dim) (transformer.cc:828)."""
    return x / math.sqrt(x.shape[-1])


@register("multi_head_attention", jit=True)
def multi_head_attention(q, k, v, mask=None, *, heads=1, dropout=0.0, causal=False,
                         use_flash=None, sm_scale=None):
    """Batched SDPA: q/k (N, L, H*D), v (N, L, H*Dv), Dv = D unless the values
    are narrower than the keys (latent attention's plain form: 192 and 128);
    scores times ``sm_scale`` (default ``1/sqrt(D)``). On TPU the
    unmasked/causal path runs the
    flash-attention Pallas kernel (ops/pallas/flash_attention.py); padding-mask
    and non-TPU paths use the XLA composite.

    Causal masking convention (``causal=True``): when Lq != Lk the mask is
    **bottom-right aligned** — query row i attends keys ``j <= i + (Lk - Lq)``,
    so the LAST query row always sees every key. This is the standard
    KV-cache / flash-attention convention (query rows are the trailing
    positions of the key sequence) and a no-op for Lq == Lk, but it differs
    from a top-left ``tril``: with a top-left mask the FIRST query row sees
    only key 0. Changed in round 5 (see CHANGELOG.md); cross-length causal
    callers that want the old top-left behaviour should pass an explicit
    ``mask=jnp.tril(jnp.ones((Lq, Lk), bool))`` instead of ``causal=True``."""
    N, Lq, HD = q.shape
    D = HD // heads
    Dv = v.shape[-1] // heads
    stated = sm_scale
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    qh = q.reshape(N, Lq, heads, D).transpose(0, 2, 1, 3)
    kh = k.reshape(N, -1, heads, D).transpose(0, 2, 1, 3)
    vh = v.reshape(N, -1, heads, Dv).transpose(0, 2, 1, 3)
    if use_flash is None:
        from .pallas.flash_attention import _on_tpu
        use_flash = mask is None and Lq == kh.shape[2] and _on_tpu()
    if use_flash and mask is None and Lq == kh.shape[2]:
        from .pallas.flash_attention import flash_attention
        out = flash_attention(qh, kh, vh, causal=causal, sm_scale=sm_scale)
        return out.transpose(0, 2, 1, 3).reshape(N, Lq, heads * Dv)
    if mask is None:
        # same dense SDPA the flash op's sub-tile fallback uses — one copy
        from .pallas.flash_attention import _dense_attention
        out = _dense_attention(qh, kh, vh, float(sm_scale), causal)
        return out.transpose(0, 2, 1, 3).reshape(N, Lq, heads * Dv)
    att = jnp.einsum("nhld,nhmd->nhlm", qh, kh,
                     preferred_element_type=jnp.float32)
    att = att / math.sqrt(D) if stated is None else att * stated
    if causal:
        # bottom-right aligned for Lq != Lk, same convention as
        # _dense_attention (the last query row sees every key)
        Lk = kh.shape[2]
        cm = jnp.tril(jnp.ones((Lq, Lk), bool), k=Lk - Lq)
        att = jnp.where(cm, att, -jnp.inf)
    if mask is not None:
        att = jnp.where(mask.astype(bool), att, -jnp.inf)
    p = jax.nn.softmax(att, axis=-1).astype(q.dtype)
    out = jnp.einsum("nhlm,nhmd->nhld", p, vh,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return out.transpose(0, 2, 1, 3).reshape(N, Lq, heads * Dv)


# ---------------------------------------------------------------------------
# Present-day decoder blocks: rotary positions, grouped KV heads under a
# block mask, routed experts (no reference op: TPU-era extensions like RMSNorm)
# ---------------------------------------------------------------------------
_MASKED = -1e30      # underflows to an exactly-zero softmax weight in f32


def yarn_mscale(factor, mscale=1.0):
    """YaRN's attention temperature for a context stretched ``factor``
    times: ``0.1 * mscale * ln(factor) + 1`` (1 at or under a factor of 1).
    A model multiplies its softmax scale by the square of the one taken at
    ``mscale_all_dim``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_frequencies(dim, theta, scaling=None):
    """(the ``dim // 2`` inverse frequencies as float32 numpy, what cos and
    sin are multiplied by). ``scaling`` None: ``theta ** (-2i / dim)`` and 1.
    Else YaRN's (a mapping with ``factor``, ``original_max_position_
    embeddings``, ``beta_fast``, ``beta_slow``, ``mscale``, ``mscale_all_dim``):
    each frequency blended between itself and itself / ``factor`` by a linear
    ramp over the pair index, from the pair that makes ``beta_fast`` turns in
    the original context (kept as it is, and every faster one) to the pair
    that makes ``beta_slow`` (divided, and every slower one); cos and sin
    times ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
    mscale_all_dim)``, or times ``attention_factor`` where a configuration
    states that number itself."""
    import numpy as onp
    exps = onp.arange(0, dim, 2, dtype=onp.float64) / dim
    inv = float(theta) ** -exps
    if not scaling:
        return inv.astype(onp.float32), 1.0
    sc = dict(scaling)
    factor = float(sc["factor"])
    span = float(sc["original_max_position_embeddings"])

    def pair_of(turns):     # the pair index whose wavelength makes ``turns``
        return dim * math.log(span / (turns * 2 * math.pi)) \
            / (2 * math.log(float(theta)))

    low = max(math.floor(pair_of(sc.get("beta_fast", 32))), 0)
    high = min(math.ceil(pair_of(sc.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = onp.clip((onp.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    inv = inv * (1.0 - ramp) + inv / factor * ramp
    scale = sc.get("attention_factor")
    if scale is None:
        scale = yarn_mscale(factor, sc.get("mscale", 1.0)) \
            / yarn_mscale(factor, sc.get("mscale_all_dim", 0.0) or 0.0)
    return inv.astype(onp.float32), float(scale)


@register("rotary_embedding", jit=True)
def rotary_embedding(x, positions, *, theta=10000.0, scaling=None):
    """Rotary positions over the whole of ``x``'s last axis (a model that
    rotates a slice of its head passes the slice), rotate-half: ``x`` (..., S,
    heads, D), ``positions`` (..., S) int. ``scaling``: None, or YaRN's
    settings (:func:`rotary_frequencies`). Angles and the rotation are
    float32; the result has ``x``'s dtype."""
    D = x.shape[-1]
    if scaling:
        inv, scale = rotary_frequencies(D, theta, scaling)
    else:   # on the device in float32: a host's float64 powers round apart
        inv = float(theta) ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
        scale = 1.0
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[..., None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    xa = x.astype(jnp.float32)
    half = jnp.concatenate([-xa[..., D // 2:], xa[..., :D // 2]], -1)
    return (xa * cos + half * sin).astype(x.dtype)


@register("block_attention", jit=True)
def block_attention(q, k, v, positions, ctx_acc=None, ctx_max=None,
                    ctx_sum=None, *, heads, kv_heads, block_length=1,
                    sm_scale=None, window=None):
    """Attention of rows to their own blocks and to everything before them.

    ``q`` (B, S, heads*D), ``k`` (B, S, kv_heads*D), ``v`` (B, S,
    kv_heads*Dv; Dv = D but for a latent row, whose values are its first
    columns): the rows' own projections, ``positions`` (B, S) int32; scores
    times ``sm_scale`` (default ``1/sqrt(D)``). Query head h attends with KV
    head ``h // (heads / kv_heads)``. A row at position i sees a row at
    position j iff ``j // block_length <= i // block_length``: both
    directions inside a block, causal between blocks (``block_length`` 1 is
    the causal mask). Under a ``window`` it sees only the last ``window``
    positions, itself among them: ``i - j < window`` besides.

    ``ctx_acc`` (B, S, heads, Dv), ``ctx_max``, ``ctx_sum`` (B, S, heads), if
    given, are a second part every row also attends to, already reduced to
    its unnormalised output, the maximum of its scores and the sum of their
    exponentials under that maximum, all float32: a decode step's cached
    context, as ``ops/pallas/paged_attention.py`` returns it. The two parts
    are merged flash-style under their common maximum. Scores, the softmax
    over both parts and the accumulation are float32; the probabilities are
    cast to ``q``'s dtype for the product with the values."""
    B, S, _ = q.shape
    G = heads // kv_heads
    D = q.shape[-1] // heads
    Dv = v.shape[-1] // kv_heads
    qh = q.reshape(B, S, kv_heads, G, D)
    blk = positions // block_length                        # (B, S)
    mask = blk[:, None, :] <= blk[:, :, None]              # (B, q, k)
    if window is not None:
        mask &= positions[:, :, None] - positions[:, None, :] < window
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qh, k.reshape(B, S, kv_heads, D),
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(D) if sm_scale is None else s * sm_scale
    s = jnp.where(mask[:, :, None, None], s, _MASKED)
    top = s.max(-1)                                        # (B, q, h, g)
    if ctx_acc is not None:
        ctx_max = ctx_max.reshape(top.shape)
        top = jnp.maximum(top, ctx_max)
    e = jnp.exp(s - top[..., None])
    denom = e.sum(-1)
    out = jnp.einsum("bqhgk,bkhd->bqhgd", e.astype(q.dtype),
                     v.reshape(B, S, kv_heads, Dv),
                     preferred_element_type=jnp.float32)
    if ctx_acc is not None:
        w = jnp.exp(ctx_max - top)
        denom = denom + ctx_sum.reshape(top.shape) * w
        out = out + ctx_acc.reshape(out.shape) * w[..., None]
    out = out / denom[..., None]
    return out.reshape(B, S, heads * Dv).astype(q.dtype)


_GMM_WEIGHT_TILE_BYTES = 4 << 20     # of a (K, N) tile; Mosaic holds two
_GMM_RESULT_TILE_COLS = 4096         # of a float32 (tm, N) tile; it holds three


def _gmm_tiling(m, k, n, itemsize):
    """(tm, tk, tn) for the Pallas grouped matmul, or None where it does not
    apply. A group's whole (K, N) matrix is one tile where it fits (3 MiB at
    2048 x 768 in bfloat16): at 16 rows a group the product is bound by
    reading each held expert's weights once, and one long DMA a group reads
    them at 600 GB/s on a v5e where (128, 128, 128) tiles reach 80 and the
    compiler's own ``ragged_dot`` 200-240 (microbenchmark, PR 28). A matrix
    that does not fit is cut along K, and a result wider than
    ``_GMM_RESULT_TILE_COLS`` along N too (a (128, 7168) float32 result tile,
    its accumulator and two weight tiles pass the 16 MiB a kernel may hold):
    7168 x 2048 goes as (128, 896, 2048) and 2048 x 7168 as (128, 512, 3584),
    3.5 MiB of weights a DMA either way; an expert's matrix is still read
    once, a column block after the other. Swept on a v5e at 16 held experts
    (PR 32): these two read 654 and 670 GB/s at a step's 2 rows an expert and
    636 and 594 at a prefill pass's 128, the best or within 3% of it over
    five legal tilings each ((128, 1792, 1024) 612 and 596, (128, 2048, 512)
    689 and 506; tiles that hold more than 16 MiB are refused). 2304 x 896
    (PR 34) is one tile a group again, 4,128,768 B, 1.6% under the limit
    (the down-projection's 896 x 2304 the same bytes): on a v5e, 64 experts
    held, each of a layer's three kernels reads its 63 drawn experts' tiles
    at 750 GB/s at a step's 4 rows an expert (0.347 ms a kernel;
    ``expert_ffn_roofline_pct.decode`` 91-92) and computes a prefill pass's
    32,768 pairs, 512 rows an expert, at 135 (gate, up) and 129 (down)
    TFLOP/s, 1.00 and 1.05 ms a kernel (my chip runs, PR 34)."""
    tm = 128 if m % 128 == 0 else m if m < 128 and m % 16 == 0 else None
    if tm is None or k % 128 or n % 128:
        return None
    tn = n
    while tn > _GMM_RESULT_TILE_COLS and tn % 256 == 0:
        tn //= 2
    tk = k
    while tk * tn * itemsize > _GMM_WEIGHT_TILE_BYTES and tk % 256 == 0:
        tk //= 2
    return (tm, tk, tn) if tk * tn * itemsize <= _GMM_WEIGHT_TILE_BYTES \
        else None


def _grouped_matmul(rows, w, sizes):
    """``rows`` (M, K), sorted by group, times each group's ``w`` (G, K, N):
    (M, N) float32; rows past ``sizes.sum()`` are not computed (whatever they
    hold is masked by the caller). On a TPU in bfloat16 the Pallas grouped
    matmul of ``jax.experimental`` (megablox) at :func:`_gmm_tiling`; anywhere
    else ``lax.ragged_dot``. Chosen from ``jax.default_backend()`` at trace
    time, as ``flash_attention`` is."""
    tiling = None
    if jax.default_backend() == "tpu" and rows.dtype == jnp.bfloat16:
        tiling = _gmm_tiling(rows.shape[0], rows.shape[1], w.shape[2],
                             rows.dtype.itemsize)
    if tiling is None:
        return lax.ragged_dot(
            rows, w, sizes, preferred_element_type=jnp.float32,
            precision=(lax.Precision.DEFAULT if rows.dtype == jnp.bfloat16
                       else lax.Precision.HIGHEST))
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    # the kernel's dot names no precision and would inherit the package's
    # global "highest", which Mosaic rejects on bfloat16 operands ("Bad lhs
    # type"); one MXU pass is exact for them
    with jax.default_matmul_precision("bfloat16"):
        return gmm(rows, w, sizes, preferred_element_type=jnp.float32,
                   tiling=tiling)


def route_softmax(x, router, *, top_k, norm_topk=True):
    """(weights (T, top_k) float32, experts (T, top_k) int32): a float32
    softmax of ``x @ router`` over all E experts, the ``top_k`` largest,
    divided by their sum under ``norm_topk``."""
    probs = jax.nn.softmax(
        jnp.dot(x, router, preferred_element_type=jnp.float32), -1)
    top_p, top_i = lax.top_k(probs, top_k)
    if norm_topk:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    return top_p, top_i


def route_grouped_sigmoid(x, router, bias, *, top_k, n_group, topk_group,
                          norm_topk=True, scale=1.0):
    """(weights, experts) as :func:`route_softmax`, by the group-limited
    sigmoid rule: scores ``s = sigmoid(x @ router)`` in float32; the choice is
    made on ``c = s + bias`` (a learned correction (E,) that moves the choice
    and never the weight): the E experts lie in ``n_group`` equal groups, a
    group's score is the sum of its two largest ``c``, the ``topk_group`` best
    groups are kept and of their experts the ``top_k`` largest ``c``. The
    weights are the chosen experts' ``s``, divided by their sum under
    ``norm_topk``, times ``scale``."""
    T = x.shape[0]
    s = jax.nn.sigmoid(jnp.dot(x, router, preferred_element_type=jnp.float32))
    c = s + bias.astype(jnp.float32)
    by_group = c.reshape(T, n_group, -1)
    group_score = lax.top_k(by_group, 2)[0].sum(-1)            # (T, groups)
    _, kept = lax.top_k(group_score, topk_group)
    keep = jnp.zeros((T, n_group), bool).at[
        jnp.arange(T)[:, None], kept].set(True)
    c = jnp.where(keep[:, :, None], by_group, -jnp.inf).reshape(T, -1)
    _, top_i = lax.top_k(c, top_k)
    top_s = jnp.take_along_axis(s, top_i, -1)
    if norm_topk:
        top_s = top_s / top_s.sum(-1, keepdims=True)
    return top_s * scale, top_i


# (row, expert) pairs one pass of the grouped products takes where a chip
# holds a share of the experts: a pass's rows, its three float32 products and
# its weighted result are this long, whatever the rows x top_k routed in all
_PAIR_BLOCK = 2048
# pairs one pass takes where a chip holds every expert: a prompt of more rows
# goes a block of whole rows at a time (a 16,384-row prefill routes 131,072
# pairs, whose float32 down-projection alone would be 1.2 GB at 2,304 wide).
# A pass reads every expert's weights once, so the block is as long as the
# scratch allows: at 32,768 pairs of 2,304 x 896 a pass computes for 2 ms
# what it reads in 1
_ALL_HELD_PAIRS = 32768
_PAIR_SUMS = _telemetry.counter(
    "mxtpu_moe_pair_sums_total",
    "Expert passes lowered, by how their (row, expert) pairs reach their "
    "rows: row_gather = each row reads its own pairs' results once, in its "
    "own order (one pass over the rows: a step, a prompt of a chip that "
    "holds every expert); share_passes = a share's pairs 2,048 at a time, "
    "each pass added to the rows as a product with their 0/1 matrix. "
    "Counted when a program is traced, not when it runs.",
    labelnames=("form",))


def _expert_pass(x, order, sizes, w_gate, w_up, w_down, top_k):
    """The three grouped products over the pairs ``order`` names (sorted by
    expert, ``sizes`` a held expert): (pairs, H) float32, unweighted; rows
    past ``sizes.sum()`` are not computed and hold whatever they hold."""
    rows = x[order // top_k]
    gate = _grouped_matmul(rows, w_gate, sizes)
    up = _grouped_matmul(rows, w_up, sizes)
    return _grouped_matmul((jax.nn.silu(gate) * up).astype(x.dtype), w_down,
                           sizes)


def expert_ffn(x, top_w, top_i, w_gate, w_up, w_down, *, first_expert=0,
               all_held=False):
    """The held experts' part of a routed SwiGLU layer over rows ``x`` (T, H),
    given each row's ``top_k`` experts ``top_i`` and weights ``top_w``:
    (result (T, H), rows routed to each held expert (E_held,) int32).

    ``w_gate``, ``w_up`` (E_held, H, F) and ``w_down`` (E_held, F, H) are the
    experts this chip holds, ``first_expert`` onward; rows routed elsewhere add
    nothing here. No row is dropped and there is no capacity: the (row,
    expert) pairs are sorted by expert, the held experts' first, and each
    expert multiplies exactly its own rows (:func:`_grouped_matmul`).
    ``all_held`` says every expert is here: all T x top_k pairs are then
    gathered at once, or, past ``_ALL_HELD_PAIRS`` of them, the pairs of a
    block of whole rows at a time (each block is this function over its
    rows, written into its place of one result: a row's pairs stay
    together; a step's few hundred pairs go as ever). Of a share, the pairs
    routed here are gathered ``_PAIR_BLOCK`` at a time, as many passes as
    they fill, and each pass is added to its rows: the work and the scratch
    grow with the rows routed here, not with all that were routed (a
    sixteenth of the experts see a sixteenth of 8 x 4,096 prefill rows,
    whose gathered rows and float32 products would be 1.9 GB).

    How a pass's pairs reach their rows. Where all the pairs go in one pass
    (every expert held, or a share's step or short prompt), each row reads
    its own pairs' float32 results where they sorted to and sums them,
    weighted, in its own order ``k = 0, 1, ...``, in float32, rounded to
    ``x.dtype`` once (:func:`~mxnet_tpu.ops.pallas.pair_sum.pair_sum`): the
    down-projection's result is read once and nothing of its size is
    written besides it (on a v5e at 32,768 pairs of 2,304: 0.6 ms a pass
    where a weighted copy, a copy in pair order and the row sum took 2.9;
    PERF.md). A pair that was not computed (routed elsewhere, or a padded
    row's) is never read. A row's result depends on its own pairs alone,
    whatever the rest of the batch holds (the step's bitwise contract). A
    share's passes keep the product of each pass with the (row, pair) 0/1
    matrix: their bound is scratch, not bytes, for holding all of a share's
    pair results for one read would take T x top_k x H float32 (805 MB at a
    16,384-row prompt of 4 pairs a row at 3,072 wide). That product costs T
    x a pass's pairs x H, which a share keeps at a sixteenth of T x T x
    top_k x H and a chip that holds every expert would pay in full (128
    experts of 2,048 x 768, 8 a row, on a v5e: 3.53 ms in one pass against
    4.09 in passes at 1,024 rows, 2.84 against 3.00 at 512; PERF.md), and it
    sums a row's pairs in the order they sorted to. Accumulation is
    float32."""
    T, H = x.shape
    top_k = top_i.shape[1]
    held = w_gate.shape[0]
    pairs = T * top_k
    if all_held and pairs > _ALL_HELD_PAIRS:
        rows = _ALL_HELD_PAIRS // top_k
        pad = -T % rows         # rows that route nowhere and weigh nothing

        def blocks(a, fill):
            a = jnp.pad(a, ((0, pad), (0, 0)), constant_values=fill)
            return a.reshape(-1, rows, a.shape[1])

        xs, ws, ids = blocks(x, 0), blocks(top_w, 0), blocks(top_i, -1)

        # each block's rows written into their place of one result: stacked
        # and then cut, the results left 0.7 GB more temporaries in a
        # 16,384-row prefill of 2,304-wide rows (compiled for a v5e)
        def one_block(b, carry):
            out, total = carry
            part, load = expert_ffn(xs[b], ws[b], ids[b], w_gate, w_up,
                                    w_down, first_expert=first_expert,
                                    all_held=True)
            return (lax.dynamic_update_slice_in_dim(out, part, b * rows, 0),
                    total + load)

        out, sizes = lax.fori_loop(
            0, xs.shape[0], one_block,
            (jnp.zeros((T + pad, H), x.dtype), jnp.zeros((held,), jnp.int32)))
        return out[:T], sizes
    expert = top_i.reshape(-1) - first_expert
    here = (expert >= 0) & (expert < held)
    expert = jnp.where(here, expert, held)          # elsewhere: sorted last
    order = jnp.argsort(expert, stable=True)
    sizes = jnp.bincount(expert, length=held + 1)[:held].astype(jnp.int32)
    if all_held or pairs <= _PAIR_BLOCK:
        _PAIR_SUMS.labels("row_gather").inc()
        from .pallas.pair_sum import pair_sum
        y = _expert_pass(x, order, sizes, w_gate, w_up, w_down, top_k)
        # where each of a row's pairs sorted to; a pair routed elsewhere (or
        # a padded row's) was not computed and is never read
        pos = jnp.argsort(order).reshape(T, top_k)
        return pair_sum(y, pos, expert.reshape(T, top_k), top_w, sizes,
                        x.dtype), sizes
    _PAIR_SUMS.labels("share_passes").inc()
    weight = jnp.where(here, top_w.reshape(-1), 0.0)[order]
    ends = jnp.cumsum(sizes)
    pad = -pairs % _PAIR_BLOCK      # the last pass's slice stays inside
    order = jnp.pad(order, (0, pad))
    weight = jnp.pad(weight, (0, pad))

    def one_pass(i, out):
        at = i * _PAIR_BLOCK
        idx = lax.dynamic_slice_in_dim(order, at, _PAIR_BLOCK)
        # what of each expert's run of pairs lies in this pass
        part = jnp.clip(jnp.minimum(ends, at + _PAIR_BLOCK)
                        - jnp.maximum(ends - sizes, at), 0)
        w = lax.dynamic_slice_in_dim(weight, at, _PAIR_BLOCK)
        y = _expert_pass(x, idx, part, w_gate, w_up, w_down, top_k)
        # rows past the held experts' pairs are not computed: whatever they hold
        live = jnp.arange(_PAIR_BLOCK) < part.sum()
        y = jnp.where(live[:, None], y * w[:, None], 0.0)
        # each pair's result to its row, as a product with the 0/1 matrix of
        # (row, pair): on a v5e a scatter-add of 2,048 rows of 7,168 into
        # 4,096 takes 3.9 ms, the two products 1.4 (PERF.md, PR 32).
        # bfloat16 operands carry the float32 result in two parts, so
        # nothing is rounded before the sum
        mine = (jnp.arange(T)[:, None] == (idx // top_k)[None, :]) \
            .astype(x.dtype)
        parts = [y.astype(x.dtype)]
        if x.dtype == jnp.bfloat16:
            parts.append((y - parts[0].astype(jnp.float32)).astype(x.dtype))
        for part_y in parts:
            out = out + jnp.dot(
                mine, part_y, preferred_element_type=jnp.float32,
                precision=(lax.Precision.DEFAULT if x.dtype == jnp.bfloat16
                           else lax.Precision.HIGHEST))
        return out

    out = lax.fori_loop(0, -(-ends[-1] // _PAIR_BLOCK), one_pass,
                        jnp.zeros((T, H), jnp.float32))
    return out.astype(x.dtype), sizes


@register("moe_ffn", jit=True)
def moe_ffn(x, router, w_gate, w_up, w_down, router_bias=None, *, top_k,
            norm_topk=True, first_expert=0, n_group=None, topk_group=None,
            routed_scale=1.0):
    """Routed SwiGLU experts over rows ``x`` (T, H): returns (the part of the
    layer's result that the held experts give (T, H), rows routed to each held
    expert (E_held,) int32).

    ``router`` (H, E) scores every expert, by one of two rules: a float32
    softmax over all E (:func:`route_softmax`), or, where ``router_bias`` (E,)
    and ``n_group`` / ``topk_group`` are given, sigmoid scores chosen within
    the best groups under the correction bias and scaled by ``routed_scale``
    (:func:`route_grouped_sigmoid`). Either feeds the one expert product,
    :func:`expert_ffn`, with the experts this chip holds, ``first_expert``
    onward of the router's E."""
    if router_bias is None:
        top_w, top_i = route_softmax(x, router, top_k=top_k,
                                     norm_topk=norm_topk)
    else:
        top_w, top_i = route_grouped_sigmoid(
            x, router, router_bias, top_k=top_k, n_group=n_group,
            topk_group=topk_group, norm_topk=norm_topk, scale=routed_scale)
    return expert_ffn(x, top_w, top_i, w_gate, w_up, w_down,
                      first_expert=first_expert,
                      all_held=w_gate.shape[0] == router.shape[1])


# ---------------------------------------------------------------------------
# CTC loss (nn/ctc_loss.cc)
# ---------------------------------------------------------------------------
@register("CTCLoss", jit=True)
def ctc_loss(data, label, data_lengths=None, label_lengths=None, *,
             use_data_lengths=False, use_label_lengths=False, blank_label="first"):
    """CTC forward loss via the standard log-alpha recursion under lax.scan.
    data: (T, N, C) unnormalised; label: (N, L) classes (0 reserved for blank when
    blank_label='first', matching the reference default)."""
    T, N, C = data.shape
    L = label.shape[1]
    logp = jax.nn.log_softmax(data.astype(jnp.float32), axis=-1)
    blank = 0 if blank_label == "first" else C - 1
    lab = label.astype(jnp.int32)
    if blank_label == "last":
        lab = lab  # labels already 0-based
    else:
        pass
    # extended label seq: blank, l1, blank, l2, ... blank  (length 2L+1)
    S = 2 * L + 1
    ext = jnp.full((N, S), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(lab)
    lab_len = (label_lengths.astype(jnp.int32) if use_label_lengths and
               label_lengths is not None else jnp.sum(
                   (lab != blank) & (lab >= 0), axis=1).astype(jnp.int32))
    dat_len = (data_lengths.astype(jnp.int32) if use_data_lengths and
               data_lengths is not None else jnp.full((N,), T, jnp.int32))
    ext_len = 2 * lab_len + 1
    neg_inf = -1e30
    alpha0 = jnp.full((N, S), neg_inf)
    alpha0 = alpha0.at[:, 0].set(logp[0, :, blank])
    alpha0 = alpha0.at[:, 1].set(jnp.take_along_axis(logp[0], ext[:, 1:2], axis=1)[:, 0])

    same = jnp.concatenate([jnp.zeros((N, 2), bool),
                            ext[:, 2:] == ext[:, :-2]], axis=1)

    def step(alpha, t):
        a1 = alpha
        a2 = jnp.concatenate([jnp.full((N, 1), neg_inf), alpha[:, :-1]], axis=1)
        a3 = jnp.concatenate([jnp.full((N, 2), neg_inf), alpha[:, :-2]], axis=1)
        a3 = jnp.where(same, neg_inf, a3)
        m = jnp.maximum(jnp.maximum(a1, a2), a3)
        new = m + jnp.log(jnp.exp(a1 - m) + jnp.exp(a2 - m) + jnp.exp(a3 - m) + 1e-37)
        emit = jnp.take_along_axis(logp[t], ext, axis=1)
        new = new + emit
        new = jnp.where((t < dat_len)[:, None], new, alpha)
        return new, None

    alpha, _ = lax.scan(step, alpha0, jnp.arange(1, T))
    idx_last = ext_len - 1
    a_last = jnp.take_along_axis(alpha, idx_last[:, None], axis=1)[:, 0]
    a_prev = jnp.take_along_axis(alpha, jnp.maximum(idx_last - 1, 0)[:, None], axis=1)[:, 0]
    m = jnp.maximum(a_last, a_prev)
    ll = m + jnp.log(jnp.exp(a_last - m) + jnp.exp(a_prev - m))
    return (-ll).astype(data.dtype)


# ---------------------------------------------------------------------------
# spatial transformer family (src/operator/spatial_transformer.cc,
# grid_generator.cc, bilinear_sampler.cc)
# ---------------------------------------------------------------------------
@register("GridGenerator", jit=True)
def grid_generator(data, *, transform_type="affine", target_shape=(0, 0)):
    """Sampling-grid generation (grid_generator.cc). 'affine': data is
    (N, 6) affine matrices -> grid (N, 2, H, W) of (x, y) coords in [-1, 1];
    'warp': data is (N, 2, H, W) flow added to the identity grid."""
    h, w = target_shape
    if transform_type == "affine":
        if h <= 0 or w <= 0:
            raise ValueError("GridGenerator(affine) requires a positive "
                             f"target_shape, got {target_shape}")
        n = data.shape[0]
        theta = data.reshape(n, 2, 3).astype(jnp.float32)
        ys = jnp.linspace(-1.0, 1.0, h)
        xs = jnp.linspace(-1.0, 1.0, w)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        ones = jnp.ones_like(gx)
        base = jnp.stack([gx.ravel(), gy.ravel(), ones.ravel()])  # (3, H*W)
        out = jnp.einsum("nij,jp->nip", theta, base)              # (n, 2, H*W)
        return out.reshape(n, 2, h, w).astype(data.dtype)
    if transform_type == "warp":
        n, _, fh, fw = data.shape
        ys = jnp.linspace(-1.0, 1.0, fh)
        xs = jnp.linspace(-1.0, 1.0, fw)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        # flow is in pixels; normalize to the [-1, 1] grid scale
        norm = jnp.stack([data[:, 0] * 2.0 / jnp.maximum(fw - 1, 1),
                          data[:, 1] * 2.0 / jnp.maximum(fh - 1, 1)], axis=1)
        ident = jnp.stack([gx, gy])[None]
        return (ident + norm).astype(data.dtype)
    raise ValueError(f"GridGenerator: unknown transform_type {transform_type!r}")


@register("BilinearSampler", jit=True)
def bilinear_sampler(data, grid, *, cudnn_off=False):
    """Sample data (N, C, H, W) at grid (N, 2, OH, OW) of normalized (x, y)
    in [-1, 1], zero padding outside (bilinear_sampler.cc) — one vectorized
    4-corner gather, shared with DeformableConvolution."""
    from .contrib import _bilinear_sample_nchw
    n, c, h, w = data.shape
    oh, ow = grid.shape[2], grid.shape[3]
    px = (grid[:, 0] + 1.0) * (w - 1) / 2.0
    py = (grid[:, 1] + 1.0) * (h - 1) / 2.0
    sampled = _bilinear_sample_nchw(data.astype(jnp.float32),
                                    py.reshape(n, -1).astype(jnp.float32),
                                    px.reshape(n, -1).astype(jnp.float32))
    return sampled.reshape(n, oh, ow, c).transpose(0, 3, 1, 2) \
        .astype(data.dtype)


@register("SpatialTransformer", jit=True)
def spatial_transformer(data, loc, *, target_shape=(0, 0),
                        transform_type="affine", sampler_type="bilinear",
                        cudnn_off=False):
    """Affine spatial transformer network head (spatial_transformer.cc):
    localization output -> sampling grid -> bilinear sample."""
    if sampler_type != "bilinear":
        raise ValueError("SpatialTransformer: only sampler_type='bilinear' "
                         f"is supported (got {sampler_type!r})")
    grid = grid_generator(loc, transform_type=transform_type,
                          target_shape=tuple(target_shape))
    return bilinear_sampler(data, grid)


@register("BatchNorm_v1", jit=True)
def batch_norm_v1(x, gamma, beta, moving_mean, moving_var, **attrs):
    """Legacy alias kept for backcompat (src/operator/batch_norm_v1.cc);
    identical semantics to BatchNorm on this stack."""
    return batch_norm(x, gamma, beta, moving_mean, moving_var, **attrs)


@register("_contrib_SparseEmbedding", jit=True)
def sparse_embedding(indices, weight, *, input_dim=0, output_dim=0,
                     dtype="float32", deterministic=False, **legacy_attrs):
    """Deprecated alias (contrib SparseEmbedding): Embedding with
    sparse_grad=True. Tolerates legacy serialized attrs (deterministic);
    the tape's sparse-cotangent path recognizes this op name directly."""
    return embedding(indices, weight, input_dim=input_dim,
                     output_dim=output_dim, dtype=dtype, sparse_grad=True)


# ---------------------------------------------------------------------------
# legacy regression output heads (src/operator/regression_output.cc).
# Forward is an activation of the data; the *gradient w.r.t. data* is the
# regression residual scaled by grad_scale / num_output — the incoming
# cotangent is ignored, exactly like SoftmaxOutput above
# (regression_output-inl.h:196-208: num_output = label.Size()/batch).
# ---------------------------------------------------------------------------
def _regression_output(data, label, grad_scale, fwd_fn, residual_fn):
    @jax.custom_vjp
    def f(x, ll):
        return fwd_fn(x)

    def f_fwd(x, ll):
        out = fwd_fn(x)
        return out, (out, ll)

    def f_bwd(res, g):
        out, ll = res
        llb = ll.reshape(out.shape).astype(out.dtype)
        num_output = out.size // out.shape[0] if out.ndim > 0 else 1
        dx = residual_fn(out, llb) * (grad_scale / num_output)
        return dx.astype(out.dtype), None

    f.defvjp(f_fwd, f_bwd)
    return f(data, label)


@register("LinearRegressionOutput")
def linear_regression_output(data, label, *, grad_scale=1.0):
    return _regression_output(data, label, grad_scale,
                              lambda x: x, lambda o, l: o - l)


@register("LogisticRegressionOutput")
def logistic_regression_output(data, label, *, grad_scale=1.0):
    return _regression_output(data, label, grad_scale,
                              jax.nn.sigmoid, lambda o, l: o - l)


@register("MAERegressionOutput")
def mae_regression_output(data, label, *, grad_scale=1.0):
    return _regression_output(data, label, grad_scale,
                              lambda x: x, lambda o, l: jnp.sign(o - l))


# ---------------------------------------------------------------------------
# round-4 op tail: MakeLoss, SVMOutput, Correlation — the last genuine
# absences from the registry name-diff (VERDICT r3 missing #5).
# ---------------------------------------------------------------------------
@register("MakeLoss")
def make_loss(data, *, grad_scale=1.0, valid_thresh=0.0, normalization="null",
              **legacy_attrs):
    """Turn any expression into a loss head (src/operator/make_loss.cc):
    forward is identity; the gradient w.r.t. data is grad_scale (the incoming
    cotangent is ignored, like every legacy *Output head), divided by the
    batch size ('batch') or by count(data > valid_thresh) ('valid')."""

    @jax.custom_vjp
    def f(x):
        return x

    def f_fwd(x):
        return x, x

    def f_bwd(x, g):
        scale = jnp.asarray(grad_scale, jnp.float32)
        if normalization == "batch":
            scale = scale / x.shape[0]
        elif normalization == "valid":
            n_valid = jnp.maximum(
                jnp.sum(x > valid_thresh).astype(jnp.float32), 1.0)
            scale = scale / n_valid
        return (jnp.full(x.shape, scale, x.dtype),)

    f.defvjp(f_fwd, f_bwd)
    return f(data)


@register("make_loss")
def make_loss_alias(data, **attrs):
    """Lowercase alias (tensor/elemwise_unary_op_basic.cc make_loss)."""
    return make_loss(data, **attrs)


@register("SVMOutput")
def svm_output(data, label, *, margin=1.0, regularization_coefficient=1.0,
               use_linear=False):
    """One-vs-all hinge-loss head (src/operator/svm_output.cc). Forward is
    identity over the scores (batch, classes); the gradient w.r.t. data is
    the L2-SVM (default) or L1-SVM (use_linear) subgradient, ignoring the
    incoming cotangent (svm_output.cc:31-66 L1_SVM/L2_SVM kernels)."""

    @jax.custom_vjp
    def f(x, ll):
        return x

    def f_fwd(x, ll):
        return x, (x, ll)

    def f_bwd(res, g):
        x, ll = res
        xa = x.astype(jnp.float32)
        reg = jnp.float32(regularization_coefficient)
        onehot = jax.nn.one_hot(ll.astype(jnp.int32), x.shape[-1],
                                dtype=jnp.float32)
        if use_linear:  # L1-SVM
            d_true = -reg * (margin > xa).astype(jnp.float32)
            d_other = reg * (margin > -xa).astype(jnp.float32)
        else:  # L2-SVM
            d_true = -2.0 * reg * jnp.maximum(margin - xa, 0.0)
            d_other = 2.0 * reg * jnp.maximum(margin + xa, 0.0)
        dx = onehot * d_true + (1.0 - onehot) * d_other
        return dx.astype(x.dtype), None

    f.defvjp(f_fwd, f_bwd)
    return f(data, label)


@register("Correlation", jit=True)
def correlation(data1, data2, *, kernel_size=1, max_displacement=1, stride1=1,
                stride2=1, pad_size=0, is_multiply=True):
    """FlowNet correlation layer (src/operator/correlation.cc): for every
    displacement (dy, dx) in a (2*max_displacement/stride2+1)^2 grid, the
    channel-and-window-summed product (or |difference|) of the two padded
    feature maps, normalized by kernel_size^2 * C.

    TPU-native formulation: one statically-unrolled displacement loop of
    elementwise products + a shared reduce_window sum — XLA fuses the
    products and lowers the window sums onto the VPU; gradients come from
    jax.vjp (no hand-written backward as in the CUDA kernel)."""
    b, c, h, w = data1.shape
    kr = (kernel_size - 1) // 2           # kernel radius
    border = max_displacement + kr
    f1 = jnp.pad(data1.astype(jnp.float32),
                 ((0, 0), (0, 0), (pad_size, pad_size), (pad_size, pad_size)))
    # data2 gets an extra max_displacement ring so every shift is a static
    # zero-padded slice (no wrap-around)
    md = max_displacement
    f2 = jnp.pad(data2.astype(jnp.float32),
                 ((0, 0), (0, 0), (pad_size + md, pad_size + md),
                  (pad_size + md, pad_size + md)))
    hp, wp = h + 2 * pad_size, w + 2 * pad_size
    displacements = range(-md, md + 1, stride2)
    maps = []
    for dy in displacements:
        for dx in displacements:
            shifted = jax.lax.slice(
                f2, (0, 0, md + dy, md + dx), (b, c, md + dy + hp, md + dx + wp))
            m = f1 * shifted if is_multiply else jnp.abs(f1 - shifted)
            maps.append(jnp.sum(m, axis=1))          # channel sum -> (B,Hp,Wp)
    stack = jnp.stack(maps, axis=1)                   # (B, D^2, Hp, Wp)
    # window sum centered at y1 = y*stride1 + border: slice off the
    # displacement border, then a VALID KxK window sum with stride1
    core = stack[:, :, md:hp - md, md:wp - md]
    summed = jax.lax.reduce_window(
        core, 0.0, jax.lax.add, (1, 1, kernel_size, kernel_size),
        (1, 1, stride1, stride1), "valid")
    out = summed / float(kernel_size * kernel_size * c)
    return out.astype(data1.dtype)
