"""Share of the chip's idle time in the traced window that no ``decode.*``
span of the program covers: idle gaps of at least ``xplane.BETWEEN_OPS_NS``
against the ring's spans moved onto the trace's clock. What is left over is
idle time that the program's own spans cannot explain."""
from chipbench.layer_metrics import _program_spans

NAME = "idle_outside_spans_pct.decode"
UNIT = "%"
LAYER = "serving host"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    spans = _program_spans.ring("decode.")
    join = _program_spans.clock_join(run, spans)
    if join is None:
        return None
    outside, idle = _program_spans.idle_outside(run, spans, join)
    return 100.0 * outside / idle if idle else None
