"""The paged-attention kernel's share of its roofline in its latent mode: what
the absorbed attention of the traced window's steps must read and compute
(each live cached position's row **once** a layer, 576 numbers as keys and
values both; every head's scores on the row and weights on its first 512
columns; ``models/<family>.py``) over the kernel's device time inside the
step's program (``jit_decode``) in that window. The live positions are the
``ctx_live`` attrs of the ``decode.step`` spans the program left in the ring
while the window stood open (the driver notes when, on the host's clock), at
their mean over as many steps as the trace holds with the kernel in them; per
step the least time is the larger of bytes over the HBM peak and FLOPs over
the bf16 peak. The bytes count a live position once, so a kernel that read
the pool as K and again as V could read at most 50% where bytes bind; at 128
heads on one row the kernel is bound by the MXU (28% on a v5e, PERF.md, PR
32) and the share cannot tell the two apart: the single buffer and the single
DMA a page are held by the kernel's tests. The stored row is 640 wide and the
required 576: the kernel's own DMA moves a ninth more than is counted here."""
from chipbench.layer_metrics import _kernels, _peaks, _program_spans

NAME = "latent_attention_roofline_pct.decode"
UNIT = "%"
LAYER = "kernels, embeddings"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    label = run.get("latent_attention_op")
    found = _kernels.inside_modules(run, "jit_decode") if label else None
    peaks = _peaks.of(run) if found else None
    if not peaks or not run.get("traced_window_host_s"):
        return None
    ops = [(at, dur) for name, at, dur in found[1] if name == label]
    steps = len({at for at, _ in ops})
    opened, closed = (1e9 * t for t in run["traced_window_host_s"])
    live = [s["attrs"]["ctx_live"] for s in _program_spans.ring("decode.step")
            if opened <= s["start"] <= closed and "ctx_live" in s["attrs"]]
    if not steps or not live:
        return None
    positions = sum(live) / len(live)
    least_s = steps * max(
        positions * run["latent_bytes_per_position"]
        / peaks["hbm_bytes_per_s"],
        positions * run["latent_flops_per_position"]
        / peaks["bf16_flops_per_s"])
    return 100.0 * least_s / (sum(dur for _, dur in ops) / 1e9)
