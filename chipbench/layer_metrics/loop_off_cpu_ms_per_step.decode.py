"""Milliseconds a pass the decode loop's thread stood without the processor
while **not** waiting for the chip (the interpreter lock, the scheduler's
lock, the runtime), profiler off: over the ``decode.iteration`` spans that
ran a step, the span's wall time less its thread's CPU time (``dur_us`` −
``cpu_us``), less the ``decode.fetch`` spans under it that began before
their result was there (``ready`` 0): their wall time, less their thread's
CPU time where they took it (the program's do not: a fetch that waits for
the chip is on the processor for under 0.1 ms, and a read of the thread's
clock costs the v5e host 5-7 us). The mean over the passes, a sum over a
sum, and not their median: that clock ticks in 10 ms there, so one pass's
``cpu_us`` is 0 or 10,000 and only the sums over a window's passes say
something. None where the passes carry no ``cpu_us``."""
from chipbench.layer_metrics import _passes, _program_spans

NAME = "loop_off_cpu_ms_per_step.decode"
UNIT = "ms"
LAYER = "serving host"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    off = _program_spans.decode_profiler_off(run)
    cpu = _passes.cpu_ns()
    passes = _passes.stepped(off or ())
    stood = []
    for top, fetches in _passes.under(off or (), "decode.fetch").items():
        if top not in cpu:
            continue
        it = passes[top]
        stood.append(it["end"] - it["start"] - cpu[top] - sum(
            s["end"] - s["start"] - cpu.get(s["span_id"], 0.0)
            for s in fetches if not s["attrs"].get("ready")))
    if len(stood) < _program_spans.MIN_SPANS:
        return None
    return sum(stood) / len(stood) / 1e6
