"""Share of decode-step rows that produced a token a client received: tokens
other than first tokens received in the untraced window, over the decode
steps DecodeStats counted in it times ``max_batch_size`` (the stats keep
occupancy only as a last-value gauge)."""
NAME = "batch_occupancy_pct.decode"
UNIT = "%"
LAYER = "serving host"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    if not run.get("decode_steps"):
        return None
    return 100.0 * run["later_tokens"] / \
        (run["decode_steps"] * run["max_batch_size"])
