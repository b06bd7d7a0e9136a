"""Share of the traced window in which no op ran on the chip (the worst chip
of the cell): 1 - union of device-op intervals / window."""
from chipbench.layer_metrics._device import idle_pct

NAME = "device_idle_pct.train"
UNIT = "%"
LAYER = "device"
MOVES = "train_samples_per_s"
KINDS = ("train",)


def read(run):
    return idle_pct(run)
