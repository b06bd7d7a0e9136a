"""Share of the traced window in which no op ran on the chip: 1 - union of
device-op intervals / window."""
from chipbench.layer_metrics._device import idle_pct

NAME = "device_idle_pct.decode"
UNIT = "%"
LAYER = "device"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    return idle_pct(run)
