#!/usr/bin/env python
"""Operator micro-benchmark harness (parity: benchmark/opperf/ —
run_performance_test + the category runners + the opperf.py CLI, collapsed
into one TPU-native module).

Times eager dispatch of registered ops (forward, and backward where the op is
differentiable) with proper device sync, reporting avg/p50/max µs per op —
the tool that exposes dispatch overhead and slow kernels. The category suites
mirror the reference's nd_operations/* groupings with TPU-relevant default
shapes (batched, MXU-aligned).

Usage:
    python benchmark/opperf.py                      # standard suite
    python benchmark/opperf.py --ops dot,exp,sum    # specific ops
    python benchmark/opperf.py --json results.json
"""
import argparse
import json
import os
import sys
import time

# runnable as a plain script from anywhere: the package lives one level up
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp


# op -> (input shapes, attrs); shapes chosen MXU/VPU-friendly (128-multiples)
_SUITES = {
    "unary": {
        "exp": ([(1024, 1024)], {}),
        "log": ([(1024, 1024)], {}),
        "sqrt": ([(1024, 1024)], {}),
        "negative": ([(1024, 1024)], {}),
        "sigmoid": ([(1024, 1024)], {}),
        "tanh": ([(1024, 1024)], {}),
        "relu": ([(1024, 1024)], {}),
    },
    "binary": {
        "broadcast_add": ([(1024, 1024), (1024, 1024)], {}),
        "broadcast_mul": ([(1024, 1024), (1024, 1024)], {}),
        "broadcast_div": ([(1024, 1024), (1, 1024)], {}),
        "elemwise_add": ([(1024, 1024), (1024, 1024)], {}),
    },
    "gemm": {
        "dot": ([(1024, 1024), (1024, 1024)], {}),
        "batch_dot": ([(32, 256, 256), (32, 256, 256)], {}),
        "FullyConnected": ([(128, 1024), (1024, 1024), (1024,)],
                           {"num_hidden": 1024}),
    },
    "reduction": {
        "sum": ([(1024, 1024)], {}),
        "mean": ([(1024, 1024)], {}),
        "max": ([(1024, 1024)], {}),
        "norm": ([(1024, 1024)], {}),
    },
    "nn": {
        "Convolution": ([(32, 64, 56, 56), (64, 64, 3, 3), (64,)],
                        {"kernel": (3, 3), "num_filter": 64, "pad": (1, 1)}),
        "Pooling": ([(32, 64, 56, 56)],
                    {"kernel": (2, 2), "pool_type": "max", "stride": (2, 2)}),
        "BatchNorm": ([(32, 64, 56, 56), (64,), (64,), (64,), (64,)], {}),
        "softmax": ([(128, 1024)], {}),
        "Dropout": ([(128, 1024)], {"p": 0.5}),
    },
    "indexing": {
        "take": ([(1024, 512), (256,)], {}),
        "Embedding": ([(128, 64), (30000, 256)],
                      {"input_dim": 30000, "output_dim": 256}),
        "one_hot": ([(1024,)], {"depth": 1000}),
    },
    "sorting": {
        "sort": ([(1024, 1024)], {}),
        "argsort": ([(1024, 1024)], {}),
        "topk": ([(1024, 1024)], {"k": 10}),
    },
}


def _make_inputs(op_name, shapes, rng):
    from mxnet_tpu import nd
    arrays = []
    for i, s in enumerate(shapes):
        if op_name in ("take",) and i == 1:
            a = nd.array(rng.randint(0, 1024, s).astype("int32"))
        elif op_name == "Embedding" and i == 0:
            a = nd.array(rng.randint(0, 30000, s).astype("int32"))
        elif op_name == "one_hot":
            a = nd.array(rng.randint(0, 1000, s).astype("int32"))
        else:
            a = nd.array(rng.rand(*s).astype("float32"))
        arrays.append(a)
    return arrays


def _first_out(out):
    return out[0] if isinstance(out, (list, tuple)) else out


def _fetch(arr):
    """Close a timing window by fetching a VALUE (PERF.md round 3 saw
    block_until_ready alone return early)."""
    return float(arr.data.ravel()[0])


def _amortized_us(call, close, runs, rtt_us=0.0, windows=5):
    """Median over `windows` of: ((run `call` x runs, then one closing value
    fetch) - fetch RTT) / runs. Measures steady-state eager throughput with
    async dispatch overlapping device work — the reference engine's semantics
    (ops return immediately; SURVEY §3.1) — without putting a host<->device
    round trip inside every iteration. The closing fetch's own round-trip
    latency (`rtt_us`) is subtracted so the number reflects the ops."""
    meds = []
    for _ in range(windows):
        t0 = time.perf_counter_ns()
        for _ in range(runs):
            out = call()
        close(out)
        meds.append(max(0.0, (time.perf_counter_ns() - t0) / 1e3 - rtt_us) / runs)
    meds.sort()
    return meds[len(meds) // 2]


def _fetch_rtt_us(ctx, samples=7):
    """Min round-trip of fetching one value of an already-computed tiny array:
    the constant any closing fetch adds (min = stable floor)."""
    from mxnet_tpu import nd
    a = nd.ones((2,), ctx=ctx)
    _fetch(a)
    ts = []
    for _ in range(samples):
        t0 = time.perf_counter_ns()
        _fetch(a)
        ts.append((time.perf_counter_ns() - t0) / 1e3)
    return min(ts)


def run_performance_test(op_names=None, warmup=5, runs=25, backward=True,
                         ctx=None):
    """Benchmark ops by name; returns a list of result dicts
    (run_performance_test analog, benchmark/opperf/utils/benchmark_utils.py).

    Two columns per direction:
      - dispatch p50: host time for one eager invoke (async; what Python pays)
      - amortized avg: wall time per call over a window closed by a value
        fetch (includes device execution; the honest throughput number)
    """
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu.ops import registry

    flat = {}
    for suite in _SUITES.values():
        flat.update(suite)
    if op_names:
        sel = {}
        for name in op_names:
            if name not in flat:
                raise KeyError(f"no benchmark config for op {name!r}; "
                               f"known: {sorted(flat)}")
            sel[name] = flat[name]
        flat = sel

    rng = onp.random.RandomState(7)
    results = []
    with (ctx if ctx is not None else mx.current_context()) as run_ctx:
        rtt = _fetch_rtt_us(run_ctx)
        for name, (shapes, attrs) in flat.items():
            op = registry.get_op(name)
            arrays = _make_inputs(name, shapes, rng)

            def fwd():
                return registry.invoke(op, arrays, dict(attrs))

            for _ in range(warmup):
                out = fwd()
            _fetch(_first_out(out))
            disp = []
            for _ in range(runs):
                t0 = time.perf_counter_ns()
                fwd()
                disp.append((time.perf_counter_ns() - t0) / 1e3)
            _fetch(_first_out(fwd()))
            # amortized windows use >=100 calls so RTT jitter stays small
            # against the window total
            win = max(runs, 100)
            amort_f = _amortized_us(fwd, lambda o: _fetch(_first_out(o)), win, rtt)

            row = {"operator": name,
                   "dispatch_p50_forward_us": round(float(onp.percentile(disp, 50)), 2),
                   "avg_time_forward_us": round(amort_f, 2),
                   "inputs": [list(s) for s in shapes]}

            if backward and op.differentiable:
                for a in arrays:
                    if str(a.dtype).startswith("float"):
                        a.attach_grad()
                grads = [a for a in arrays if a.grad is not None]

                def bwd():
                    with autograd.record():
                        head = _first_out(registry.invoke(op, arrays, dict(attrs)))
                    head.backward()
                    return grads[0] if grads else head

                for _ in range(warmup):
                    g = bwd()
                if grads:
                    _fetch(g.grad if g.grad is not None else g)
                    amort_b = _amortized_us(
                        bwd, lambda g: _fetch(g.grad if g.grad is not None else g),
                        win, rtt)
                    row["avg_time_backward_us"] = round(amort_b, 2)
            results.append(row)
    return results


def main():
    from mxnet_tpu import cache
    cache.enable_compile_cache()
    parser = argparse.ArgumentParser(description="mxnet_tpu operator perf")
    parser.add_argument("--ops", default=None,
                        help="comma-separated op names (default: full suite)")
    parser.add_argument("--runs", type=int, default=25)
    parser.add_argument("--warmup", type=int, default=5)
    parser.add_argument("--no-backward", action="store_true")
    parser.add_argument("--ctx", default=None, choices=["cpu", "tpu"],
                        help="context to benchmark on (default: the chip; "
                             "cpu under JAX_PLATFORMS=cpu)")
    parser.add_argument("--json", default=None, help="write results to file")
    args = parser.parse_args()
    ops = args.ops.split(",") if args.ops else None

    import mxnet_tpu as mx
    ctx = {"cpu": mx.cpu(0), "tpu": mx.tpu(0)}[args.ctx] if args.ctx \
        else mx.runtime.measurement_context()
    print(f"context: {ctx} -> {ctx.jax_device()}")
    res = run_performance_test(ops, warmup=args.warmup, runs=args.runs,
                               backward=not args.no_backward, ctx=ctx)
    widths = (24, 18, 16, 16)
    hdr = ("operator", "fwd dispatch p50", "fwd amort avg", "bwd amort avg")
    print("".join(h.ljust(w) for h, w in zip(hdr, widths)))
    for r in res:
        print("".join([
            r["operator"].ljust(widths[0]),
            str(r["dispatch_p50_forward_us"]).ljust(widths[1]),
            str(r["avg_time_forward_us"]).ljust(widths[2]),
            str(r.get("avg_time_backward_us", "-")).ljust(widths[3])]))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(res, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
