"""Milliseconds per training step: the median ``step_n`` dispatch of the
untraced window, by the host's clock, over its K steps."""
import statistics

NAME = "step_ms.train"
UNIT = "ms"
LAYER = "train step"
MOVES = "train_samples_per_s"
KINDS = ("train",)


def read(run):
    if not run.get("dispatch_s"):
        return None
    return 1e3 * statistics.median(run["dispatch_s"]) \
        / run["steps_per_dispatch"]
