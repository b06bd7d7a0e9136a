"""Decision-gate experiment for the conv+BN Pallas epilogue work (round 4).

Compares the fused Pallas kernel (prologue affine+relu, 1x1 GEMM, moment
epilogue — mxnet_tpu/ops/pallas/fused_conv1x1.py) against the identical
unfused XLA chain on every distinct 1x1-conv shape of ResNet-50 at batch 128.
Timing: amortized windows closed by a value fetch (PERF.md methodology).

Run on the TPU host:  python benchmark/fused_conv_experiment.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp
import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas.fused_conv1x1 import (
    conv1x1_bn_act, conv1x1_bn_act_reference)

# (label, M = batch*H*W, K = Cin, N = Cout) — ResNet-50 v1 @224, batch 128
SHAPES = [
    ("s2_reduce", 128 * 56 * 56, 64, 64),
    ("s2_expand", 128 * 56 * 56, 64, 256),
    ("s2_in", 128 * 56 * 56, 256, 64),
    ("s3_in", 128 * 28 * 28, 512, 128),
    ("s3_expand", 128 * 28 * 28, 128, 512),
    ("s4_in", 128 * 14 * 14, 1024, 256),
    ("s4_expand", 128 * 14 * 14, 256, 1024),
    ("s5_in", 128 * 7 * 7, 2048, 512),
    ("s5_expand", 128 * 7 * 7, 512, 2048),
]


CHAIN = 100


def _chained(fn):
    """Run CHAIN dependent kernel invocations inside ONE jit: the
    per-dispatch floor would otherwise swamp sub-ms kernels. The
    1e-30*acc feedback serializes iterations without changing values, and
    consuming y[0,0] keeps the y write live in the XLA reference (a real
    network always materializes y)."""
    @jax.jit
    def run(x, w, s, t):
        def body(i, carry):
            x_, acc = carry
            y, cs, cq = fn(x_, w, s, t)
            acc = acc + cs[0] + cq[0] + y[0, 0].astype(jnp.float32)
            x_ = x + (1e-30 * acc).astype(x.dtype)
            return (x_, acc)
        _, acc = jax.lax.fori_loop(0, CHAIN, body, (x, jnp.float32(0.0)))
        return acc
    return run


_RTT_MS = None


def _rtt_ms():
    """Dispatch+fetch floor of a trivial jitted computation (the constant
    every timed window carries)."""
    global _RTT_MS
    if _RTT_MS is None:
        f = jax.jit(lambda a: a * 2.0)
        z = jnp.float32(1.0)
        float(f(z))
        ts = []
        for _ in range(7):
            t0 = time.perf_counter()
            float(f(z))
            ts.append((time.perf_counter() - t0) * 1e3)
        _RTT_MS = min(ts)
        print(f"dispatch+fetch floor: {_RTT_MS:.1f} ms (subtracted)")
    return _RTT_MS


def _amortize(run, args, windows=5):
    rtt = _rtt_ms()
    _ = float(run(*args))
    meds = []
    for _w in range(windows):
        t0 = time.perf_counter()
        _ = float(run(*args))
        meds.append(max((time.perf_counter() - t0) * 1e3 - rtt, 0.0) / CHAIN)
    meds.sort()
    return meds[len(meds) // 2]


def main():
    from mxnet_tpu import cache
    cache.enable_compile_cache()
    rng = onp.random.RandomState(0)
    jax.jit(lambda: jnp.zeros(()))()  # wake the backend
    print(f"{'shape':12s} {'M':>8s} {'K':>5s} {'N':>5s} "
          f"{'XLA ms':>8s} {'Pallas ms':>10s} {'speedup':>8s}")
    tot_x = tot_p = 0.0
    for label, m, k, n in SHAPES:
        x = jnp.asarray(rng.rand(m, k).astype("float32") - 0.3, jnp.bfloat16)
        w = jnp.asarray(rng.rand(k, n).astype("float32") * 0.05, jnp.bfloat16)
        s = jnp.asarray(rng.rand(k).astype("float32") + 0.5)
        t = jnp.asarray(rng.rand(k).astype("float32") - 0.5)
        bm = 448 if m % 448 == 0 else 512
        tx = _amortize(_chained(conv1x1_bn_act_reference), (x, w, s, t))
        tp = _amortize(
            _chained(lambda *a: conv1x1_bn_act(*a, block_m=bm)), (x, w, s, t))
        tot_x += tx
        tot_p += tp
        print(f"{label:12s} {m:8d} {k:5d} {n:5d} {tx:8.3f} {tp:10.3f} "
              f"{tx / tp:7.2f}x")
    print(f"{'TOTAL':12s} {'':8s} {'':5s} {'':5s} {tot_x:8.3f} {tot_p:10.3f} "
          f"{tot_x / tot_p:7.2f}x")


if __name__ == "__main__":
    main()
