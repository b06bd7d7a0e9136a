"""Share of the rows the prefills ran that were padding, profiler off:
``100 × (1 − Σ tokens ÷ Σ bucket)`` over the ``decode.prefill`` spans that
ended before the traced window opened and launched something (``tokens`` >
0: a prompt shorter than a block launches nothing). ``tokens`` is the
prompt's rows, ``bucket`` the rung of the ladder they ran at."""
from chipbench.layer_metrics import _program_spans

NAME = "prefill_padding_pct.decode"
UNIT = "%"
LAYER = "endpoints"
MOVES = "decode_tokens_per_s"
KINDS = ("decode",)


def read(run):
    off = _program_spans.decode_profiler_off(run)
    ran = [s["attrs"] for s in off or () if s["name"] == "decode.prefill"
           and s["attrs"].get("tokens", 0) > 0 and "bucket" in s["attrs"]]
    if len(ran) < _program_spans.MIN_SPANS:
        return None
    return 100.0 * (1.0 - sum(a["tokens"] for a in ran)
                    / sum(a["bucket"] for a in ran))
