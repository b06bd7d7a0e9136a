"""95th percentile over all gaps between consecutive tokens of one request that
clients received in the untraced window. A tail of a
system held at capacity: it swings too far from run to run to carry a bound
(PERF.md, PR 23), so it stands here and not among the end-to-end metrics."""
NAME = "tpot_p95_ms"
UNIT = "ms"
LAYER = "serving host"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    return run.get("tpot_p95_ms")
