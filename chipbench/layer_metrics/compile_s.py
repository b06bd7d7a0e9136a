"""Seconds JAX spent tracing, lowering and compiling or fetching from its
persistent cache during set-up (``jax.monitoring`` events)."""
NAME = "compile_s"
UNIT = "s"
LAYER = "compile and caches"
MOVES = "setup_s"
KINDS = ("train", "decode")


def read(run):
    spent = run.get("setup_compile")
    if not spent:
        return None
    return spent["trace_s"] + spent["lower_s"] + spent["backend_s"]
