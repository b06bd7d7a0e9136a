"""The prefill's attention kernel's share of its roofline: for every call of
the flash kernel inside a prefill program (``jit_prefill``) in the traced
window, the causal attention of the rung it ran at (half the square of the
rung's rows, every head, keys of 192 and values of 128: ``models/<family>.py``
gives each rung's FLOPs and bytes and the kernel's name there) over the time
it took: the sum of the least times (the larger of FLOPs over the bf16 peak
and bytes over the HBM peak) over the sum of the calls' device time. The rungs
under 512 rows go the dense way and have no call of their own."""
from chipbench.layer_metrics import _kernels, _peaks

NAME = "prefill_attention_roofline_pct.decode"
UNIT = "%"
LAYER = "kernels, embeddings"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    rungs = run.get("prefill_attention_ops")
    found = _kernels.inside_modules(run, "jit_prefill") if rungs else None
    peaks = _peaks.of(run) if found else None
    if not peaks:
        return None
    least_s = took_s = 0.0
    for name, _, dur in found[1]:
        if name in rungs:
            flops, nbytes = rungs[name]
            least_s += max(flops / peaks["bf16_flops_per_s"],
                           nbytes / peaks["hbm_bytes_per_s"])
            took_s += dur / 1e9
    return 100.0 * least_s / took_s if took_s else None
