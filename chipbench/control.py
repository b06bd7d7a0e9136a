"""The control of a decode cell's ``correct``: it has to come out as not
correct.

    python -m chipbench.control --workload <cell> --seed <n> --seconds <s>

runs the cell as ``python -m chipbench`` does (the same set-up, load and
window) and, after the answers' check, reads the same sampled requests once
more with the family's ``control_logits`` (the plain reference in the nearest
precision below the configuration's) in the program's place. The control need
not decode: at each served position of the same prompts and tokens it takes
the token the lower precision puts first and reads how far that token's logit
lies below the reference's best. The widest such gap is what the cell's
``worst_logit_deficit`` would read of a program computing in that precision,
so it has to lie above ``logit_tolerance``; the limit is set between the
program's readings and this one (PERF.md section 2). The benchmark's own runs
never come here.
"""
import sys

import numpy as onp

from . import harness
from .drivers import decode_closed


def control_reading(bench, lm, family, done):
    """What the control reads on the sample ``_check_requests`` took."""
    picks, toks = decode_closed.sampled_rows(bench, done)
    config = bench.config
    ref = decode_closed.served_logits(
        done, picks, toks,
        lambda rows: family.reference_logits(lm, config, rows))
    low = decode_closed.served_logits(
        done, picks, toks,
        lambda rows: family.control_logits(lm, config, rows))
    worst, positions, other = 0.0, 0, 0
    for (_, here), (_, lower) in zip(ref, low):
        first = onp.asarray(lower, onp.float32).argmax(-1)
        gaps = here.max(-1) - here[onp.arange(len(first)), first]
        worst = max(worst, float(gaps.max()))
        positions += len(first)
        other += int((first != here.argmax(-1)).sum())
    limit = bench.cell["logit_tolerance"]
    return {"worst_logit_deficit": worst, "logit_tolerance": limit,
            "comes_out_not_correct": worst > limit, "positions": positions,
            "positions_with_another_first_token": other}


def main(argv=None):
    check = decode_closed._check_requests

    def check_then_control(bench, lm, family, done, vocab):
        ok, seen = check(bench, lm, family, done, vocab)
        bench.say({"control": control_reading(bench, lm, family, done)})
        return ok, seen

    decode_closed._check_requests = check_then_control
    try:
        return harness.main(argv)
    finally:
        decode_closed._check_requests = check


if __name__ == "__main__":
    sys.exit(main())
