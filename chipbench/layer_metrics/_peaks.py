"""Shared by the decode readers that set a run against the chip's peaks
(``peaks.json``, by device kind)."""
import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def of(run):
    """The peaks of the run's device kind; None for a run that names no kind
    (an older driver) or on the CPU (a rehearsal: no chip, no share). A kind
    the table lacks is an error, not a default."""
    kind = run.get("device_kind")
    if kind is None or kind.lower().startswith("cpu"):
        return None
    with open(_PEAKS) as f:
        peaks = json.load(f)["by_device_kind"]
    if kind not in peaks:
        raise KeyError(f"peaks.json has no device kind {kind!r}")
    return peaks[kind]


def roofline_pct(run, flops, nbytes, device_ms):
    """The least time the chip could take for ``flops`` and ``nbytes`` (the
    larger of the two bounds) over the ``device_ms`` it took, in percent."""
    peaks = of(run)
    if peaks is None or not device_ms:
        return None
    least_s = max(flops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (device_ms / 1e3)
