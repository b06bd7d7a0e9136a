"""Step forwards run per token clients received, in the profiler-off window:
``DecodeStats`` steps over the tokens counted there. A block of L tokens
costs ``denoising_steps`` + 1 forwards of its lane (0.75 at L = 4 and 2
steps with every lane busy in every forward); idle lanes and a commit that
is not merged with the next block's first step raise it."""
NAME = "forwards_per_token.decode"
UNIT = "forwards/token"
LAYER = "serving host"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    if not run.get("tokens_in_window") or "rows_per_forward" not in run:
        return None
    # a forward serves every lane: per token of one lane's sequence
    lanes = run["rows_per_forward"] / run["block_length"]
    return run["decode_steps"] * lanes / run["tokens_in_window"]
