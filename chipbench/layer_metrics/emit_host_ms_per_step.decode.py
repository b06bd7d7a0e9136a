"""Milliseconds a decode pass spends handing tokens to its clients, profiler
off: per ``decode.iteration`` that ran a step, the sum of the ``decode.emit``
spans under it, the step's and each finished prefill's; the median."""
import statistics

from chipbench.layer_metrics import _passes, _program_spans

NAME = "emit_host_ms_per_step.decode"
UNIT = "ms"
LAYER = "serving host"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    off = _program_spans.decode_profiler_off(run)
    emits = _passes.under(off or (), "decode.emit")
    if len(emits) < _program_spans.MIN_SPANS:
        return None
    return statistics.median(sum(s["end"] - s["start"] for s in spans)
                             for spans in emits.values()) / 1e6
