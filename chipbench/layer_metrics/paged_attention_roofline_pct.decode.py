"""The paged-attention kernel's share of its roofline over K and V pools: what
the model's attention to its cache **requires** in the traced window's steps
over the device time of all the kernel's calls inside the step's program
(``jit_decode``) in that window. The requirement is the model's, whatever
implements it: a layer that sees everything must read every live position's K
and V rows, a layer that sees a window no more than the window's
(``models/<family>.py`` gives a position-layer's bytes and FLOPs, the driver
the layers of each kind), so a kernel that read a window layer's whole context
is charged for what it need not have read. The live positions are the
``ctx_live`` and ``ctx_window_live`` attrs of the ``decode.step`` spans the
program left in the ring while the window stood open (the driver notes when, on
the host's clock), at their means, times as many steps as the trace holds with
the kernel in them; per step the least time is the larger of bytes over the
HBM peak and FLOPs over the bf16 peak. A program without ``ctx_window_live``
(or a model without window layers) has nothing to read here."""
from chipbench.layer_metrics import _kernels, _peaks, _program_spans

NAME = "paged_attention_roofline_pct.decode"
UNIT = "%"
LAYER = "kernels, embeddings"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    label = run.get("paged_attention_op")
    found = _kernels.inside_modules(run, "jit_decode") if label else None
    peaks = _peaks.of(run) if found else None
    if not peaks or not run.get("traced_window_host_s"):
        return None
    ops = [(at, dur) for name, at, dur in found[1] if name == label]
    steps = len({at for at, _ in ops})
    opened, closed = (1e9 * t for t in run["traced_window_host_s"])
    live = [(s["attrs"]["ctx_live"], s["attrs"]["ctx_window_live"])
            for s in _program_spans.ring("decode.step")
            if opened <= s["start"] <= closed
            and "ctx_window_live" in s["attrs"] and "ctx_live" in s["attrs"]]
    if not steps or not live:
        return None
    layers = run["paged_layers"]
    # position-layers a step's attention must read
    required = (layers["full"] * sum(a for a, _ in live)
                + layers["window"] * sum(b for _, b in live)) / len(live)
    least_s = steps * max(
        required * run["paged_bytes_per_position"] / peaks["hbm_bytes_per_s"],
        required * run["paged_flops_per_position"]
        / peaks["bf16_flops_per_s"])
    return 100.0 * least_s / (sum(dur for _, dur in ops) / 1e9)
