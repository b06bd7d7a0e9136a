"""What one forward of the step must read and compute (every weight once, the
lanes' live context, 2 x active parameters a row; ``models/<family>.py``)
over the median device time of the step's program (``jit_decode``, the full
batch bucket's rows assumed) in the traced window: the larger of bytes over
the HBM peak and FLOPs over the bf16 peak."""
from chipbench.layer_metrics import _peaks
from chipbench.layer_metrics._modules import median_ms

NAME = "denoise_step_roofline_pct.decode"
UNIT = "%"
LAYER = "endpoints"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    if "forward_bytes" not in run:
        return None
    return _peaks.roofline_pct(run, run["forward_flops"],
                               run["forward_bytes"],
                               median_ms(run, "jit_decode"))
