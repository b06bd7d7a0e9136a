"""Share of the traced window that chip 0 spent in collectives: ops whose
parsed HLO opcode is all-reduce, all-gather, reduce-scatter, all-to-all or
collective-permute (of a start/done pair, the done). Cells on one chip have
no collective and report nothing."""
from chipbench import xplane

NAME = "collective_pct.train"
UNIT = "%"
LAYER = "mesh and collectives"
MOVES = "train_samples_per_s"
KINDS = ("train",)


def read(run):
    trace, info = run.get("trace"), run.get("trace_summary")
    if run["chips"] < 2 or not trace:
        return None
    dev = trace["devices"][min(trace["devices"])]
    held = xplane.op_seconds(dev, info["window"], keep=xplane.is_collective)
    return 100.0 * sum(held.values()) / info["window_s"]
