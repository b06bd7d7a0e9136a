"""Milliseconds the decode loop's thread works per pass while not blocked on
the chip, profiler off: the median, over ``decode.iteration`` spans that ran
a step and ended before the traced window opened, of the span's duration
minus the ``decode.fetch`` spans under it."""
import statistics

from chipbench.layer_metrics import _program_spans

NAME = "sched_host_ms_per_step.decode"
UNIT = "ms"
LAYER = "serving host"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    off = _program_spans.decode_profiler_off(run)
    if not off:
        return None
    parent = {s["span_id"]: s["parent_id"] for s in off}
    host = {s["span_id"]: s["end"] - s["start"] for s in off
            if s["name"] == "decode.iteration" and s["attrs"].get("rows")}
    for s in off:
        if s["name"] != "decode.fetch":
            continue
        top = s["parent_id"]
        while parent.get(top) is not None:
            top = parent[top]
        if top in host:
            host[top] -= s["end"] - s["start"]
    if len(host) < _program_spans.MIN_SPANS:
        return None
    return statistics.median(host.values()) / 1e6
