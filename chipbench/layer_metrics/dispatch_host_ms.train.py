"""Milliseconds of host time inside one ``step_n`` call, profiler off: the
median duration of the program's ``train.step_n`` spans of the measured
window (the warm-up dispatch compiles). The harness opens one
``chipbench.dispatch`` span around each call it traces and nothing is
dispatched afterwards, so the traced ones are the ring's last; a rehearsal
has no device trace and counts them too."""
import statistics

from chipbench import xplane
from chipbench.layer_metrics import _program_spans

NAME = "dispatch_host_ms.train"
UNIT = "ms"
LAYER = "train step"
MOVES = "train_samples_per_s"
KINDS = ("train",)


# a 20 s window of the four-chip cell holds ten dispatches
MIN_CALLS = 5


def read(run):
    calls = _program_spans.measured(run, _program_spans.ring("train.step_n"))
    trace = run.get("trace")
    if trace:
        traced = sum(1 for name, _, _ in trace["spans"]
                     if name == xplane.SPAN_PREFIX + "dispatch")
        calls = calls[:len(calls) - traced]
    if len(calls) < MIN_CALLS:
        return None
    return statistics.median(s["end"] - s["start"] for s in calls) / 1e6
