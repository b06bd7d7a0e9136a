"""95th percentile of the milliseconds a request waited from ``submit()``
until its prefill began, profiler off: the ``queue_wait_us`` attr of the
``decode.prefill`` spans that ended before the traced window opened."""
from chipbench.layer_metrics import _program_spans
from chipbench.stats import percentile

NAME = "queue_wait_p95_ms.decode"
UNIT = "ms"
LAYER = "serving host"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    off = _program_spans.decode_profiler_off(run)
    waits = [s["attrs"]["queue_wait_us"] / 1e3 for s in off or ()
             if s["name"] == "decode.prefill"]
    if len(waits) < _program_spans.MIN_SPANS:
        return None
    return percentile(waits, 95)
