"""Training traffic: K distinct seeded micro-batches per ``step_n`` dispatch
through ParallelTrainStep on the mesh the cell file gives, the pool of
micro-batches made once and kept on the device.

The timing pattern is bench.py's ``_time_steps``: every window is closed by
fetching an output *value* (a ready-flag sync alone has returned early). Two
dispatches are kept in flight, so the chip never waits for the host between
them, and each is closed by fetching its own losses, which are checked.
"""
import time

import numpy as onp

from ..harness import seed32, span
from ..stats import median

END_TO_END = {"train_samples_per_s": "samples/s"}
KIND = "train"               # which per-layer readers apply (their ``KINDS``)


def _dispatch(step, batches):
    with span("dispatch"):
        return step.step_n(*batches)


def _fetch(out):
    with span("fetch"):
        return onp.asarray(out.asnumpy(), onp.float64)


def _window(step, batches, enough):
    """Dispatch until ``enough(n_closed, seconds)`` says stop, two in flight.
    Returns (window seconds, seconds per dispatch, losses per dispatch);
    the window runs from the first dispatch to the last fetch."""
    losses, ends = [], []
    t0 = time.perf_counter()
    pending = _dispatch(step, batches)
    while True:
        stop = enough(len(ends) + 1, time.perf_counter() - t0)
        nxt = None if stop else _dispatch(step, batches)
        losses.append(_fetch(pending))
        ends.append(time.perf_counter())
        if nxt is None:
            break
        pending = nxt
    spans = [b - a for a, b in zip([t0] + ends[:-1], ends)]
    return ends[-1] - t0, spans, losses


def run(bench):
    import jax
    from mxnet_tpu import parallel

    cell, config = bench.cell, bench.config
    family = bench.family()
    k = int(cell["steps_per_dispatch"])
    samples = int(cell["samples_per_chip_per_step"]) * bench.chips
    spec = family.build_train(config, cell, seed32(bench.seed), bench.context)
    mesh = parallel.make_mesh(cell["mesh"], devices=bench.devices)
    step = parallel.ParallelTrainStep(
        spec.block, spec.loss, spec.optimizer, mesh,
        compute_dtype=spec.compute_dtype, extra_specs=spec.extra_specs)
    with jax.default_device(bench.devices[0]):
        raw = jax.jit(spec.make_batches, static_argnums=(1, 2))(
            jax.random.PRNGKey(seed32(bench.seed, 1)), k, samples)
    batches = step.place_batch_n(*raw)
    bench.phase_done("build")

    with jax.default_device(bench.devices[0]):
        ref_loss = spec.reference_loss(raw)
    del raw
    bench.phase_done("reference")

    # warm-up: the one shape this cell uses. Its first loss is the system's
    # loss at the initial weights, which the reference was given
    first = _fetch(_dispatch(step, batches))
    first_loss = float(first[0])
    bench.setup_done()

    c0 = bench.clock.read()["compiles"]
    window_s, spans, losses = _window(
        step, batches, lambda n, secs: secs >= bench.seconds)
    compiles = bench.clock.read()["compiles"] - c0
    dispatches = len(spans)
    rate = dispatches * k * samples / window_s

    if bench.trace:
        n = int(cell.get("trace_dispatches", 2))
        with bench.traced_window():
            _window(step, batches, lambda done, secs: done >= n)

    rel = abs(first_loss - ref_loss) / abs(ref_loss)
    finite = all(onp.all(onp.isfinite(x)) for x in [first] + losses)
    falling = float(losses[-1].mean()) < float(first.mean())
    checks = {"first_loss_near_reference": rel <= cell["first_loss_rtol"],
              "losses_finite": bool(finite), "loss_falling": bool(falling),
              "no_compile_in_window": compiles == 0}
    bench.say({"check": checks, "first_loss": first_loss,
               "reference_first_loss": ref_loss, "rel_diff": rel,
               "rtol": cell["first_loss_rtol"],
               "first_dispatch_mean_loss": float(first.mean()),
               "last_dispatch_mean_loss": float(losses[-1].mean()),
               "compiles_in_window": compiles})
    bench.say({"dispatches": dispatches, "steps_per_dispatch": k,
               "samples_per_step": samples, "window_s": window_s,
               "samples_per_s": rate,
               **{name: rate * per for name, per in spec.rates.items()},
               "dispatch_s_median": median(spans),
               "dispatch_s_min": min(spans), "dispatch_s_max": max(spans)})
    return {"correct": all(checks.values()),
            "attempted": dispatches * k,
            "failed": sum(int((~onp.isfinite(x)).sum()) for x in losses),
            "end_to_end": {"train_samples_per_s": rate},
            "compared": {
                "first_loss_rel_diff": {"value": rel,
                                        "limit": cell["first_loss_rtol"]},
                "last_dispatch_mean_loss": {"value": float(losses[-1].mean()),
                                            "limit": float(first.mean())},
                "compiles_in_window": {"value": compiles, "limit": 0}},
            # for the per-layer readers
            "dispatch_s": spans, "steps_per_dispatch": k,
            "samples_per_s": rate, "chips": bench.chips,
            "flops_per_sample": family.flops_per_sample(config, cell),
            "device_kind": bench.device_row["device_kind"]}
