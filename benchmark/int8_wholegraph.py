"""Whole-graph int8 ResNet-50 inference experiment (VERDICT r4 #7).

The round-3 int8 path lost (0.62x bf16) because every Quantized* block
round-tripped quantize -> int8 op -> dequantize in fp32. This experiment
builds the named fix: an END-TO-END int8 dataflow — activations stay int8
between layers, inference BN is folded into per-output-channel scales, and
each conv's int32 accumulator is requantized to the next layer's int8 scale
in a fused epilogue (scale-multiply + bias + ReLU + round/clip riding the
conv fusion). Residual joins add in f32 inside the epilogue and requantize
once. v5e MXU peak: ~394 TOPS int8 vs ~197 TFLOP/s bf16, so a 2x ceiling
exists IF the graph is int8-clean.

Prints JSON lines: bf16 baseline img/s, int8 whole-graph img/s, and the
int8-vs-fp32 logit cosine similarity (sanity that the graph is faithful).
"""
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp

QMAX = 127.0

# ResNet-50 v1: (blocks, c_out, c_mid, first_stride) per stage
STAGES = [(3, 256, 64, 1), (4, 512, 128, 2), (6, 1024, 256, 2),
          (3, 2048, 512, 2)]


def build_params(rng):
    """Random fp32 weights with BN pre-folded: every conv gets (w, bias)
    where w already carries gamma/sigma and bias = beta - mu*gamma/sigma."""
    def conv_w(cin, cout, k):
        w = rng.randn(cout, cin, k, k).astype("float32")
        w *= (2.0 / (cin * k * k)) ** 0.5          # He init
        scale = rng.uniform(0.5, 1.5, cout).astype("float32")  # folded BN
        bias = rng.uniform(-0.2, 0.2, cout).astype("float32")
        return w * scale[:, None, None, None], bias

    params = {"stem": conv_w(3, 64, 7)}
    cin = 64
    for si, (blocks, cout, cmid, stride) in enumerate(STAGES):
        for bi in range(blocks):
            pre = f"s{si}b{bi}"
            params[pre + "c1"] = conv_w(cin if bi == 0 else cout, cmid, 1)
            params[pre + "c2"] = conv_w(cmid, cmid, 3)
            params[pre + "c3"] = conv_w(cmid, cout, 1)
            if bi == 0:
                params[pre + "ds"] = conv_w(cin, cout, 1)
        cin = cout
    params["fc"] = (rng.randn(1000, 2048).astype("float32") * 0.02,
                    onp.zeros(1000, "float32"))
    return params


# ---------------------------------------------------------------------------
# fp32/bf16 reference forward (same folded weights) — also the calibrator
# ---------------------------------------------------------------------------
def f32_forward(params, x, collect_amax=None):
    import jax
    import jax.numpy as jnp
    from jax import lax

    dn = lax.conv_dimension_numbers((1, 1, 1, 1), (1, 1, 1, 1),
                                    ("NCHW", "OIHW", "NCHW"))

    def conv(x, name, stride=1, relu=True, add=None):
        w, b = params[name]
        p = (w.shape[2] - 1) // 2
        # accumulator dtype follows the compute dtype: forcing f32 output on
        # the bf16 run would double its conv write bytes (unfair baseline)
        y = lax.conv_general_dilated(
            x, w.astype(x.dtype), (stride, stride), [(p, p), (p, p)],
            dimension_numbers=lax.conv_dimension_numbers(
                x.shape, w.shape, ("NCHW", "OIHW", "NCHW")),
            preferred_element_type=jnp.float32 if x.dtype == jnp.float32
            else None)
        y = y + b.astype(x.dtype)[None, :, None, None]
        if add is not None:
            y = y + add.astype(y.dtype)
        if relu:
            y = jnp.maximum(y, 0)
        if collect_amax is not None:
            collect_amax(name, y)
        return y.astype(x.dtype)

    y = conv(x, "stem", stride=2)
    y = lax.reduce_window(y, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    for si, (blocks, cout, cmid, stride) in enumerate(STAGES):
        for bi in range(blocks):
            pre = f"s{si}b{bi}"
            s = stride if bi == 0 else 1
            ident = conv(y, pre + "ds", stride=s, relu=False) if bi == 0 \
                else y
            h = conv(y, pre + "c1", stride=1)
            h = conv(h, pre + "c2", stride=s)
            y = conv(h, pre + "c3", stride=1, relu=True, add=ident)
    y = y.mean(axis=(2, 3))
    wfc, bfc = params["fc"]
    return y.astype(jnp.float32) @ wfc.T.astype(jnp.float32) + bfc


# ---------------------------------------------------------------------------
# whole-graph int8 forward
# ---------------------------------------------------------------------------
def quantize_params(params, amax):
    """Per-output-channel symmetric int8 weights + all the static scales the
    int8 graph needs (python floats / numpy constants, baked into the jit)."""
    qp = {}
    for name, (w, b) in params.items():
        if name == "fc":
            qp[name] = (w, b)
            continue
        wa = onp.abs(w).max(axis=(1, 2, 3)).clip(1e-6)       # (cout,)
        qw = onp.clip(onp.round(w / wa[:, None, None, None] * QMAX),
                      -QMAX, QMAX).astype(onp.int8)
        qp[name] = (qw, wa / QMAX, b)                         # sw per channel
    return qp


def int8_forward(qp, amax, x_q, sx_in):
    """x_q int8 NCHW in, logits f32 out; activations stay int8 throughout.
    Each layer: int8 conv -> int32 acc -> fused epilogue (f32 scale + bias
    [+ residual] + ReLU + round/clip -> int8)."""
    import jax.numpy as jnp
    from jax import lax

    def qconv(x_q, sx, name, stride=1, relu=True, add=None, add_scale=None):
        qw, sw, b = qp[name]
        p = (qw.shape[2] - 1) // 2
        acc = lax.conv_general_dilated(
            x_q, jnp.asarray(qw), (stride, stride), [(p, p), (p, p)],
            dimension_numbers=lax.conv_dimension_numbers(
                x_q.shape, qw.shape, ("NCHW", "OIHW", "NCHW")),
            preferred_element_type=jnp.int32)
        s_out = float(amax[name]) / QMAX
        # fused requantize epilogue: everything below is elementwise on the
        # conv output and fuses into the conv
        m = jnp.asarray(sx * sw / s_out, jnp.float32)          # (cout,)
        y = acc.astype(jnp.float32) * m[None, :, None, None] \
            + jnp.asarray(b / s_out)[None, :, None, None]
        if add is not None:
            y = y + add.astype(jnp.float32) * (add_scale / s_out)
        if relu:
            y = jnp.maximum(y, 0)
        y = jnp.clip(jnp.round(y), -QMAX, QMAX).astype(jnp.int8)
        return y, s_out

    y, s = qconv(x_q, sx_in, "stem", stride=2)
    y = lax.reduce_window(y, jnp.int8(-128), lax.max, (1, 1, 3, 3),
                          (1, 1, 2, 2), [(0, 0), (0, 0), (1, 1), (1, 1)])
    for si, (blocks, cout, cmid, stride) in enumerate(STAGES):
        for bi in range(blocks):
            pre = f"s{si}b{bi}"
            st = stride if bi == 0 else 1
            if bi == 0:
                ident, s_id = qconv(y, s, pre + "ds", stride=st, relu=False)
            else:
                ident, s_id = y, s
            h, sh = qconv(y, s, pre + "c1")
            h, sh = qconv(h, sh, pre + "c2", stride=st)
            y, s = qconv(h, sh, pre + "c3", relu=True, add=ident,
                         add_scale=s_id)
    # head in f32: global mean of int8, then the fc
    yf = y.astype(jnp.float32).mean(axis=(2, 3)) * s
    wfc, bfc = qp["fc"]
    return yf @ jnp.asarray(wfc).T + jnp.asarray(bfc)


def _time(fn, args, reps=3, chain=40):
    """Median seconds per call. A window's closing fetch costs a fixed round
    trip, so it is amortized over ``chain`` queued calls and closed by ONE
    value fetch (a ready-flag sync alone returned early, round 3)."""
    float(fn(*args)[0, 0])
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(chain):
            out = fn(*args)
        float(out[0, 0])
        ts.append((time.perf_counter() - t0) / chain)
    return statistics.median(ts)


def main():
    from mxnet_tpu import cache
    cache.enable_compile_cache()
    batch = int(os.environ.get("I8_BATCH", 128))
    import jax
    import jax.numpy as jnp

    rng = onp.random.RandomState(0)
    params = build_params(rng)
    x = rng.rand(batch, 3, 224, 224).astype("float32") * 2 - 1

    # calibration: one fp32 forward collecting per-layer amax
    amax = {}
    small = jnp.asarray(x[:8])
    f32_forward(params, small,
                collect_amax=lambda n, y: amax.__setitem__(
                    n, float(jnp.abs(y).max())))

    qp = quantize_params(params, amax)
    sx_in = float(onp.abs(x).max()) / QMAX
    x_q = jnp.asarray(onp.clip(onp.round(x / sx_in), -QMAX, QMAX)
                      .astype(onp.int8))
    x_bf = jnp.asarray(x, jnp.bfloat16)

    # numeric sanity: int8 logits vs fp32 logits on the same weights
    lg_f32 = onp.asarray(f32_forward(params, jnp.asarray(x[:8])))
    lg_i8 = onp.asarray(jax.jit(functools.partial(int8_forward, qp, amax))(
        x_q[:8], sx_in))
    cos = float((lg_f32 * lg_i8).sum() /
                (onp.linalg.norm(lg_f32) * onp.linalg.norm(lg_i8) + 1e-9))
    top1 = float((lg_f32.argmax(1) == lg_i8.argmax(1)).mean())
    print(json.dumps({"check": "int8_vs_fp32", "cosine": round(cos, 4),
                      "top1_agreement": round(top1, 3)}), flush=True)

    # params as jit ARGUMENTS, not closure constants — baked-in constants
    # measured ~35% slower (layout/placement pessimization, and the same
    # HTTP-413 hazard the SSD pipeline hit with closure-captured data)
    params_dev = jax.tree_util.tree_map(jnp.asarray, params)

    @jax.jit
    def f_bf(prm, xb):
        return f32_forward(prm, xb)
    t_bf = _time(f_bf, (params_dev, x_bf))
    print(json.dumps({"mode": "bf16", "img_s": round(batch / t_bf, 0),
                      "ms": round(t_bf * 1e3, 2)}), flush=True)

    qp_dev = jax.tree_util.tree_map(jnp.asarray, qp)

    @jax.jit
    def f_i8(prm, xq):
        return int8_forward(prm, amax, xq, sx_in)
    t_i8 = _time(f_i8, (qp_dev, x_q))
    print(json.dumps({"mode": "int8_wholegraph",
                      "img_s": round(batch / t_i8, 0),
                      "ms": round(t_i8 * 1e3, 2),
                      "vs_bf16": round(t_bf / t_i8, 3)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
