"""Median device time of the prefill program (``jit_prefill``, all sequence
buckets) in the traced window."""
from chipbench.layer_metrics._modules import median_ms

NAME = "prefill_ms.decode"
UNIT = "ms"
LAYER = "endpoints"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    return median_ms(run, "jit_prefill")
