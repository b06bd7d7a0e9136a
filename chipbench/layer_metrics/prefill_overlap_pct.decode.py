"""Share of the prefills that were launched behind a step in flight,
profiler off: the ``decode.prefill`` spans with ``overlapped`` = 1 among
those that ended before the traced window opened. The others were launched
onto a chip that had nothing to run."""
from chipbench.layer_metrics import _program_spans

NAME = "prefill_overlap_pct.decode"
UNIT = "%"
LAYER = "serving host"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    off = _program_spans.decode_profiler_off(run)
    overlapped = [s["attrs"]["overlapped"] for s in off or ()
                  if s["name"] == "decode.prefill"
                  and "overlapped" in s["attrs"]]
    if len(overlapped) < _program_spans.MIN_SPANS:
        return None
    return 100.0 * sum(overlapped) / len(overlapped)
