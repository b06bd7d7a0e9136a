"""Shared by the readers that take a jitted program's device time from the
trace's ``XLA Modules`` line."""
import statistics

from chipbench import xplane


def median_ms(run, module):
    """Median device milliseconds of the XLA module named ``module`` (the
    jitted function's name, ``jit_<fn>``) inside the traced window, on the
    first chip; None where the trace has none."""
    trace = run.get("trace")
    if not trace:
        return None
    dev = trace["devices"][min(trace["devices"])]
    seen = xplane.module_durations(
        dev, run["trace_summary"]["window"]).get(module)
    return 1e3 * statistics.median(seen) if seen else None
