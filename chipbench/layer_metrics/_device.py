"""Shared by the readers that take the device's idle share from the trace."""


def idle_pct(run):
    """1 - busy/window on the chip that was busy least, in percent."""
    info = run.get("trace_summary")
    if not info or info["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - min(info["busy_s"].values()) / info["window_s"])
