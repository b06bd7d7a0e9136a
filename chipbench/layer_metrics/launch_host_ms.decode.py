"""Milliseconds of the decode loop's thread inside one launch of a step,
profiler off: the median duration of the ``decode.launch`` spans of
``kind="step"`` that ended before the traced window opened."""
import statistics

from chipbench.layer_metrics import _program_spans

NAME = "launch_host_ms.decode"
UNIT = "ms"
LAYER = "serving host"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    off = _program_spans.decode_profiler_off(run)
    launches = [s["end"] - s["start"] for s in off or ()
                if s["name"] == "decode.launch"
                and s["attrs"].get("kind") == "step"]
    if len(launches) < _program_spans.MIN_SPANS:
        return None
    return statistics.median(launches) / 1e6
