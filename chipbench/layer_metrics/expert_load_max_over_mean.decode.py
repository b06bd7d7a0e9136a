"""Rows routed to the busiest expert over the mean rows an expert gets, per
layer and step, from the ``moe.expert_load_max`` / ``moe.expert_load_mean``
attrs the program leaves on its ``decode.step`` spans (sums over the measured
spans): the straggler a grouped expert product waits for."""
from chipbench.layer_metrics import _program_spans

NAME = "expert_load_max_over_mean.decode"
UNIT = "x"
LAYER = "model code"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    steps = [s["attrs"] for s in _program_spans.measured(
        run, _program_spans.ring("decode.step"))
        if "moe.expert_load_mean" in s["attrs"]]
    if len(steps) < _program_spans.MIN_SPANS:
        return None
    return sum(a["moe.expert_load_max"] for a in steps) / \
        sum(a["moe.expert_load_mean"] for a in steps)
