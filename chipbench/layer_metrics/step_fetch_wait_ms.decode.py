"""Milliseconds the decode loop's thread was blocked for a step's result,
profiler off: the median ``fetch_wait_us`` of the ``decode.step`` spans that
ended before the traced window opened. What a fetch one pass late would take
off a pass."""
import statistics

from chipbench.layer_metrics import _program_spans

NAME = "step_fetch_wait_ms.decode"
UNIT = "ms"
LAYER = "serving host"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    off = _program_spans.decode_profiler_off(run)
    waits = [s["attrs"]["fetch_wait_us"] for s in off or ()
             if s["name"] == "decode.step" and "fetch_wait_us" in s["attrs"]]
    if len(waits) < _program_spans.MIN_SPANS:
        return None
    return statistics.median(waits) / 1e3
