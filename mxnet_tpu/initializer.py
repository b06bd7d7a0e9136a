"""Weight initializers (parity: python/mxnet/initializer.py — Xavier, MSRAPrelu,
Uniform, Normal, Orthogonal, Constant, One, Zero, Bilinear, LSTMBias + registry)."""
from __future__ import annotations

import functools
import json
import math
from typing import Optional

import numpy as onp

from .base import Registry, MXNetError

__all__ = ["Initializer", "Uniform", "Normal", "DeviceNormal", "Orthogonal", "Xavier", "MSRAPrelu", "FusedRNN",
           "Constant", "Zero", "One", "Bilinear", "LSTMBias", "Load", "Mixed",
           "register", "InitDesc"]

_REG = Registry("initializer")
register = _REG.register


class InitDesc(str):
    """Parameter name + attrs descriptor handed to initializers."""
    def __new__(cls, name, attrs=None, global_init=None):
        ret = super().__new__(cls, name)
        ret.attrs = attrs or {}
        ret.global_init = global_init
        return ret


class Initializer:
    def __init__(self, **kwargs):
        self._kwargs = kwargs

    def dumps(self):
        return json.dumps([self.__class__.__name__.lower(), self._kwargs])

    def __call__(self, desc, arr):
        """Initialize `arr` (NDArray) described by `desc` (InitDesc or str)."""
        if not isinstance(desc, InitDesc):
            desc = InitDesc(desc)
        init = desc.attrs.get("__init__", "")
        if init:
            klass, kwargs = json.loads(init)
            _REG.get(klass)(**kwargs)._init_impl(desc, arr)
            return
        name = desc.lower()
        if name.endswith("weight"):
            self._init_weight(desc, arr)
        elif name.endswith("bias"):
            self._init_bias(desc, arr)
        elif name.endswith("gamma"):
            self._init_one(desc, arr)
        elif name.endswith("beta"):
            self._init_zero(desc, arr)
        elif name.endswith("running_mean") or name.endswith("moving_mean"):
            self._init_zero(desc, arr)
        elif name.endswith("running_var") or name.endswith("moving_var"):
            self._init_one(desc, arr)
        else:
            self._init_default(desc, arr)

    def _init_impl(self, desc, arr):
        self._init_weight(desc, arr)

    def init_array(self, shape, dtype, name="weight"):
        from .ndarray import zeros
        arr = zeros(shape, dtype=dtype)
        self(InitDesc(name), arr)
        return arr

    # -- primitives ---------------------------------------------------------
    def _set(self, arr, np_value):
        import jax.numpy as jnp
        arr._set_data(jnp.asarray(np_value, dtype=arr.data.dtype))

    def _init_zero(self, desc, arr):
        self._set(arr, onp.zeros(arr.shape))

    def _init_one(self, desc, arr):
        self._set(arr, onp.ones(arr.shape))

    def _init_bias(self, desc, arr):
        self._set(arr, onp.zeros(arr.shape))

    def _init_weight(self, desc, arr):
        raise NotImplementedError

    def _init_default(self, desc, arr):
        self._init_weight(desc, arr)

    def __repr__(self):
        return f"{self.__class__.__name__}({self._kwargs})"


def _rng():
    # numpy RNG seeded from the framework seed chain for reproducibility
    from . import random as _r
    import jax
    key = _r.take_key()
    seed = int(jax.random.randint(key, (), 0, 2**31 - 1))
    return onp.random.RandomState(seed)


@register("uniform")
class Uniform(Initializer):
    def __init__(self, scale=0.07):
        super().__init__(scale=scale)
        self.scale = scale

    def _init_weight(self, desc, arr):
        self._set(arr, _rng().uniform(-self.scale, self.scale, arr.shape))


@register("normal")
class Normal(Initializer):
    def __init__(self, sigma=0.01):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    def _init_weight(self, desc, arr):
        self._set(arr, _rng().normal(0, self.sigma, arr.shape))


@functools.lru_cache(maxsize=None)
def _device_normal(shape, dtype, sigma, device):
    """One compiled draw per (shape, dtype, sigma, device)."""
    import jax
    import jax.numpy as jnp
    return jax.jit(
        lambda key: (sigma * jax.random.normal(key, shape, jnp.float32))
        .astype(dtype),
        out_shardings=jax.sharding.SingleDeviceSharding(device))


@register("devicenormal")
class DeviceNormal(Initializer):
    """N(0, sigma) drawn on the array's own device, in its dtype, from
    ``seed`` and the parameter's name: no host draw and no transfer, for
    models of billions of parameters (the host initialisers draw leaf by leaf
    in numpy, 7 s for 110 M). A per-name ``scales`` entry multiplies sigma
    for parameters whose name ends with its key."""

    def __init__(self, sigma=0.02, seed=0, scales=None):
        super().__init__(sigma=sigma, seed=seed, scales=scales)
        self.sigma, self.seed, self.scales = sigma, seed, dict(scales or {})

    def _init_weight(self, desc, arr):
        import zlib
        import jax
        sigma = self.sigma
        for suffix, scale in self.scales.items():
            if str(desc).endswith(suffix):
                sigma *= scale
        device = next(iter(arr.data.devices()))
        key = jax.random.fold_in(jax.random.key(self.seed, impl="rbg"),
                                 zlib.crc32(str(desc).encode()) & 0x7FFFFFFF)
        draw = _device_normal(tuple(arr.shape), arr.data.dtype, sigma, device)
        arr._set_data(draw(jax.device_put(key, device)))

    _init_default = _init_weight


@register("constant")
class Constant(Initializer):
    def __init__(self, value=0.0):
        super().__init__(value=value)
        self.value = value

    def _init_weight(self, desc, arr):
        self._set(arr, onp.full(arr.shape, self.value))

    _init_default = _init_weight


@register("zeros")
class Zero(Constant):
    def __init__(self):
        Initializer.__init__(self)
        self.value = 0.0


@register("ones")
class One(Constant):
    def __init__(self):
        Initializer.__init__(self)
        self.value = 1.0


def _fans(shape, factor_type="avg"):
    hw = 1
    for s in shape[2:]:
        hw *= s
    fan_in = (shape[1] if len(shape) > 1 else shape[0]) * hw
    fan_out = shape[0] * hw
    return fan_in, fan_out


@register("xavier")
class Xavier(Initializer):
    """Xavier/Glorot (initializer.py Xavier parity): rnd_type uniform|gaussian,
    factor_type avg|in|out."""

    def __init__(self, rnd_type="uniform", factor_type="avg", magnitude=3):
        super().__init__(rnd_type=rnd_type, factor_type=factor_type, magnitude=magnitude)
        self.rnd_type = rnd_type
        self.factor_type = factor_type
        self.magnitude = float(magnitude)

    def _init_weight(self, desc, arr):
        fan_in, fan_out = _fans(arr.shape)
        if self.factor_type == "avg":
            factor = (fan_in + fan_out) / 2.0
        elif self.factor_type == "in":
            factor = fan_in
        elif self.factor_type == "out":
            factor = fan_out
        else:
            raise MXNetError("invalid factor_type")
        scale = math.sqrt(self.magnitude / max(factor, 1.0))
        r = _rng()
        if self.rnd_type == "uniform":
            self._set(arr, r.uniform(-scale, scale, arr.shape))
        elif self.rnd_type == "gaussian":
            self._set(arr, r.normal(0, scale, arr.shape))
        else:
            raise MXNetError("invalid rnd_type")


@register("msraprelu")
class MSRAPrelu(Xavier):
    def __init__(self, factor_type="avg", slope=0.25):
        magnitude = 2.0 / (1 + slope ** 2)
        super().__init__("gaussian", factor_type, magnitude)
        self._kwargs = {"factor_type": factor_type, "slope": slope}


@register("orthogonal")
class Orthogonal(Initializer):
    def __init__(self, scale=1.414, rand_type="uniform"):
        super().__init__(scale=scale, rand_type=rand_type)
        self.scale = scale
        self.rand_type = rand_type

    def _init_weight(self, desc, arr):
        nout = arr.shape[0]
        nin = int(onp.prod(arr.shape[1:]))
        r = _rng()
        if self.rand_type == "uniform":
            tmp = r.uniform(-1.0, 1.0, (nout, nin))
        else:
            tmp = r.normal(0.0, 1.0, (nout, nin))
        u, _, v = onp.linalg.svd(tmp, full_matrices=False)
        q = u if u.shape == tmp.shape else v
        self._set(arr, self.scale * q.reshape(arr.shape))


@register("bilinear")
class Bilinear(Initializer):
    def _init_weight(self, desc, arr):
        weight = onp.zeros(arr.shape).reshape(-1)
        shape = arr.shape
        f = onp.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        for i in range(onp.prod(shape)):
            x = i % shape[3]
            y = (i // shape[3]) % shape[2]
            weight[i] = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
        self._set(arr, weight.reshape(shape))


@register("lstmbias")
class LSTMBias(Initializer):
    """Forget-gate bias = 1 (initializer.py LSTMBias)."""

    def __init__(self, forget_bias=1.0):
        super().__init__(forget_bias=forget_bias)
        self.forget_bias = forget_bias

    def _init_weight(self, desc, arr):
        b = onp.zeros(arr.shape)
        n = arr.shape[0] // 4
        b[n:2 * n] = self.forget_bias
        self._set(arr, b)

    _init_bias = _init_weight


class Load:
    """Initialize from a dict of loaded arrays, falling back to default_init."""

    def __init__(self, param, default_init=None, verbose=False):
        self.param = {k.replace("arg:", "").replace("aux:", ""): v
                      for k, v in param.items()}
        self.default_init = default_init

    def __call__(self, name, arr):
        if name in self.param:
            arr._set_data(self.param[name].data.astype(arr.data.dtype))
        elif self.default_init is not None:
            self.default_init(name, arr)
        else:
            raise MXNetError(f"Cannot init {name}: not found and no default_init")


class Mixed:
    """Pattern-dispatch initializer (initializer.py Mixed)."""

    def __init__(self, patterns, initializers):
        import re
        self.map = list(zip([re.compile(p) for p in patterns], initializers))

    def __call__(self, name, arr):
        for prog, init in self.map:
            if prog.match(name):
                init(name, arr)
                return
        raise MXNetError(f"parameter {name} did not match any pattern")


@register("fusedrnn")  # class-name key: what Initializer.dumps() emits
@register("fused_rnn")
class FusedRNN(Initializer):
    """Initialize a fused flat RNN parameter vector sub-matrix by sub-matrix
    (initializer.py FusedRNN): the inner initializer sees each W_i2h / W_h2h
    with its true 2-D shape (so Xavier fan-in/out is right), biases get
    zeros. Layout: ops/nn.py rnn_unpack_params (rnn-inl.h flat order)."""

    def __init__(self, init, num_hidden, num_layers, mode,
                 bidirectional=False, forget_bias=1.0):
        if isinstance(init, str):
            init = self._resolve(init)
        # serialize the inner init as its full dumps() payload (name +
        # kwargs) so a round-trip rebuilds it with identical settings
        super().__init__(init=init.dumps() if hasattr(init, "dumps")
                         else type(init).__name__.lower(),
                         num_hidden=num_hidden,
                         num_layers=num_layers, mode=mode,
                         bidirectional=bidirectional, forget_bias=forget_bias)
        self._init = init
        self._h = num_hidden
        self._layers = num_layers
        self._mode = mode
        self._bi = bidirectional
        self._forget_bias = forget_bias

    @staticmethod
    def _resolve(spec):
        """Registry name ('xavier') or a dumps() payload
        ('["xavier", {...}]') -> Initializer instance."""
        try:
            name, kwargs = json.loads(spec)
            return _REG.get(name)(**kwargs)
        except (ValueError, TypeError):
            return _REG.get(spec)()

    def _init_weight(self, desc, arr):
        import numpy as onp
        from .ops.nn import _num_gates
        g = _num_gates(self._mode)
        h = self._h
        d = 2 if self._bi else 1
        total = arr.size
        # infer input_size from the flat length (closed form inversion of
        # rnn_param_size)
        rest = d * (self._layers - 1) * (g * h * h * d + g * h * h) if \
            self._layers > 1 else 0
        bias_sz = self._layers * d * 2 * g * h
        first = total - rest - bias_sz
        in_sz = first // (d * g * h) - h
        out = onp.empty(total, "float32")
        off = 0
        for layer in range(self._layers):
            cur_in = in_sz if layer == 0 else h * d
            for _ in range(d):
                for shape in ((g * h, cur_in), (g * h, h)):
                    n = shape[0] * shape[1]
                    sub = onp.zeros(shape, "float32")
                    from .ndarray.ndarray import NDArray as _ND
                    tmp = _ND(sub)
                    self._init(InitDesc(str(desc) + "_weight"), tmp)
                    out[off:off + n] = tmp.asnumpy().ravel()
                    off += n
        for layer in range(self._layers):
            for _ in range(d):
                for _bias in range(2):
                    b = onp.zeros(g * h, "float32")
                    if self._mode == "lstm":
                        # forget-gate bias (gate order i, f, g, o)
                        b[h:2 * h] = self._forget_bias / 2.0
                    out[off:off + g * h] = b
                    off += g * h
        arr._set_data(__import__("jax").numpy.asarray(
            out.reshape(arr.shape), arr.data.dtype))
