"""Median device time of the decode-step program (``jit_decode``, every batch
bucket) in the traced window."""
from chipbench.layer_metrics._modules import median_ms

NAME = "decode_step_ms.decode"
UNIT = "ms"
LAYER = "endpoints"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    return median_ms(run, "jit_decode")
