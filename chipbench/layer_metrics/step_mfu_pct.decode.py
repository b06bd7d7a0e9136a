"""The whole served path's share of the chip's peak: the FLOPs one token's
forward requires (2 x active parameters + attention over its mean live
context, ``models/<family>.py``; a block's denoising steps and its commit
forward the same rows again and do not count) times tokens per second
received, over chips times the bf16 peak of ``peaks.json``."""
from chipbench.layer_metrics import _peaks

NAME = "step_mfu_pct.decode"
UNIT = "%"
LAYER = "model code"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    if "flops_per_token" not in run:
        return None
    peaks = _peaks.of(run)
    if peaks is None:
        return None
    return 100.0 * run["flops_per_token"] * run["tokens_per_s"] / \
        (peaks["bf16_flops_per_s"] * run["chips"])
