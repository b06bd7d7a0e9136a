"""The prefill programs' share of the chip's busy time: the device time of
the ``jit_prefill`` modules that started in the traced window over the
window's busy time (the union of op intervals). Says which of the two paths,
the plain-form prefill or the absorbed-form step, a change to the cell's rate
came from. The harness offers every decode run to every decode reader, so
this one reads where the driver names the prefill's own kernels by rung
(``prefill_attention_ops``: the cells whose prefill is a large share by
design) and is silent in the others."""
from chipbench import xplane

NAME = "prefill_busy_share_pct.decode"
UNIT = "%"
LAYER = "endpoints"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    trace, info = run.get("trace"), run.get("trace_summary")
    if not trace or not run.get("prefill_attention_ops"):
        return None
    chip = min(trace["devices"])
    busy_s = info["busy_s"][chip]
    took = xplane.module_durations(trace["devices"][chip],
                                   info["window"]).get("jit_prefill")
    if not busy_s or not took:
        return None
    return 100.0 * sum(took) / busy_s
