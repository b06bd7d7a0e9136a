"""The grouped-matmul kernels' share of their roofline: what one layer's
expert product must move and compute at the step's size (the routed rows
only: every held expert's weights once, the routed (row, expert) pairs in and
out; ``models/<family>.py``) over the device time of the kernels that compute
it inside the step's program (``jit_decode``) in the traced window: the
Pallas grouped matmuls (``megablox.gmm`` through ``ops/nn.py``), named as the
breakdown prints them (``custom-call[tpu_custom_call] -> f32[pairs,width]``;
the run gives each label with its calls a layer: two of the gate's and up's
shape, one of the down's), each at its median. A prefill of as many rows
prints the same labels and is left out by its module. The sort of the pairs,
the gather of their rows, ``silu x up`` and the weighted sum back are XLA
fusions around the kernels and are not in this time: work moved out of the
kernels into them would read as a gain here and shows in
``denoise_step_roofline_pct.decode``, which times the whole step."""
import bisect
import statistics

from chipbench import xplane
from chipbench.layer_metrics import _peaks

NAME = "expert_ffn_roofline_pct.decode"
UNIT = "%"
LAYER = "kernels, embeddings"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    calls, trace = run.get("expert_ops"), run.get("trace")
    if not calls or not trace:
        return None
    dev = trace["devices"][min(trace["devices"])]
    first, last = run["trace_summary"]["window"]
    steps = sorted((start, start + dur) for text, start, dur in dev["modules"]
                   if text.split("(")[0] == "jit_decode"
                   and first <= start <= last)
    seen = {label: [] for label in calls}
    for text, start, dur in dev["ops"]:
        at = bisect.bisect_right(steps, (start, float("inf"))) - 1
        if at >= 0 and start <= steps[at][1]:
            label = xplane.op_label(text)
            if label in seen:
                seen[label].append(dur)
    if any(len(seen[label]) < n for label, n in calls.items()):
        return None                  # not one whole layer's calls
    layer_ms = sum(n * statistics.median(seen[label])
                   for label, n in calls.items()) / 1e6
    return _peaks.roofline_pct(run, run["expert_flops"], run["expert_bytes"],
                               layer_ms)
