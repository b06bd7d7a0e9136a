"""Model FLOP/s utilisation: the FLOPs the configuration requires per sample
(forward + backward, no recompute; ``models/<family>.py``) times samples per
second, over chips times the bf16 peak of ``peaks.json`` for this device
kind. A kind the table lacks is an error, not a default."""
import json
import os

NAME = "mfu_pct.train"
UNIT = "%"
LAYER = "model code"
MOVES = "train_samples_per_s"
KINDS = ("train",)

_PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def read(run):
    with open(_PEAKS) as f:
        peaks = json.load(f)["by_device_kind"]
    kind = run["device_kind"]
    if kind not in peaks:
        if kind.lower().startswith("cpu"):
            return None                  # a rehearsal: no chip, no share
        raise KeyError(f"peaks.json has no device kind {kind!r}")
    peak = peaks[kind]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * run["flops_per_sample"] * run["samples_per_s"] / peak
