"""95th percentile, over the requests submitted in the untraced window, of submit to
first token at the client's ``on_token``. A tail of a
system held at capacity: it swings too far from run to run to carry a bound
(PERF.md, PR 23), so it stands here and not among the end-to-end metrics."""
NAME = "ttft_p95_ms"
UNIT = "ms"
LAYER = "serving host"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    return run.get("ttft_p95_ms")
