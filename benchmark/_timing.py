"""Shared chain-amortized timing for TPU benchmarks.

A window's closing value fetch costs a fixed round trip, which must be
amortized over many queued calls or it inflates per-call time. Recipe: warm
once, queue `chain` calls, close the window with ONE scalar value fetch (PERF.md
round 3 saw a ready-flag sync alone return early), median over `reps`.
"""
import statistics
import time


def scalar_fetch(out):
    """Cheapest honest sync: fetch one element's VALUE."""
    a = out[0] if isinstance(out, (tuple, list)) else out
    try:
        return float(a[(0,) * a.ndim])
    except TypeError:                      # framework NDArray
        return float(a.asnumpy().ravel()[0])


def time_chained(fn, args, reps=3, chain=40, fetch=scalar_fetch):
    """Median seconds per call of ``fn(*args)`` with chain amortization."""
    out = fn(*args)
    fetch(out)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(chain):
            out = fn(*args)
        fetch(out)
        ts.append((time.perf_counter() - t0) / chain)
    return statistics.median(ts)
