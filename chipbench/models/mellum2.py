"""Mellum 2 (``model_type`` ``mellum``): the program's ``MoEDecoderLM`` with
window and full attention layers at a configuration's sizes, what its forward,
its kernels and its experts cost, and the check of what the decode path served
against the plain reference (``reference/mellum2.py``).

    python -m chipbench.models.mellum2 --workload <cell> --seed <n> ...

runs the cell as ``python -m chipbench`` does and, after the check, reads the
same requests once more with stand-ins in the program's place:

- the control: the reference with both operands of every product through
  float8 (e4m3), the nearest precision below the bfloat16 the configuration
  states: at each served position it takes the token *it* puts first, read
  under the reference (``tokens_off_best_pct``): it has to come out as not
  correct;
- every fifth served token altered (``worst_logit_deficit``): as above;
- the reference with every layer full (no window): at contexts many times the
  window it puts another token first at every served position (read on the
  chip, PERF.md section 2), so it has to come out as not correct as well.
"""
import contextlib
import functools
import json
import sys

import numpy as onp

from ..reference import mellum2 as reference
from .deepseek_v3 import _read, _sample
# the same block but for its windows: the weights' names and the routed
# product's kernels in a trace (``expert_ops``, which the driver asks this
# module for) are ``sdar_moe``'s
from .sdar_moe import _float8, _weights, expert_ops  # noqa: F401

CONTROL = False          # set by this module's own command
HEAD_BLOCK = 1           # heads whose scores the check holds at once
ROW_BLOCK = 1024         # and query rows of them: (1, 1024, T) float32
SLIDING = reference.SLIDING


def rope_by_type(config):
    """``rope_parameters`` as ``MoEDecoderLM`` takes it: a theta a kind, and
    YaRN's settings where the kind has them (``attention_factor`` is what its
    cos and sin are multiplied by)."""
    out = {}
    for kind, rope in config["rope_parameters"].items():
        scaling = {k: v for k, v in rope.items()
                   if k not in ("rope_type", "rope_theta")}
        out[kind] = {"theta": float(rope["rope_theta"]),
                     "scaling": scaling if rope["rope_type"] == "yarn"
                     else None}
    return out


def build_lm(config, seed):
    """The model on the current context, every weight drawn on the device in
    the configuration's dtype from ``seed``: N(0, init_std), norms 1."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.moe_lm import MoEDecoderLM

    lm = MoEDecoderLM(
        num_layers=config["num_hidden_layers"], units=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        expert_hidden=config["moe_intermediate_size"],
        num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        vocab_size=config["vocab_size"], norm_topk=config["norm_topk_prob"],
        rms_eps=config["rms_norm_eps"], dtype=config["dtype"],
        layer_types=config["layer_types"],
        sliding_window=config["sliding_window"],
        rope_by_type=rope_by_type(config), prefix="lm_")
    # served, not trained: no gradient buffer beside each of 3.8 B weights
    lm.collect_params().setattr("grad_req", "null")
    lm.initialize(mx.init.DeviceNormal(config["init_std"], seed=seed))
    return lm


# ---------------------------------------------------------------------------
# what a forward requires (hand-counted in the tests)
# ---------------------------------------------------------------------------
def _per_row(config):
    """Parameters one row multiplies: (attention of a layer, the router, one
    expert, the head)."""
    H, D = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * D
    kv = config["num_key_value_heads"] * D
    return (2 * H * q + 2 * H * kv, H * config["num_experts"],
            3 * H * config["moe_intermediate_size"], H * config["vocab_size"])


def _width(config):
    return {"bfloat16": 2, "float32": 4}[config["dtype"]]


def layers_by_kind(config):
    """(full layers, window layers)."""
    window = sum(t == SLIDING for t in config["layer_types"])
    return config["num_hidden_layers"] - window, window


def paged_attention_flops(config, positions):
    """FLOPs one layer's attention to ``positions`` cached positions
    requires (summed over lanes): every query head scores the position's key
    and weighs its value."""
    return 4 * positions * config["num_attention_heads"] * config["head_dim"]


def paged_attention_bytes(config, positions):
    """Bytes one layer's attention must read of ``positions`` cached
    positions: each one's K row and V row, once."""
    return 2 * positions * config["num_key_value_heads"] \
        * config["head_dim"] * _width(config)


def forward_flops(config, rows, context):
    """FLOPs a forward of ``rows`` rows requires, each attending to
    ``context`` cached positions: 2 x the parameters a row multiplies (its
    ``num_experts_per_tok`` experts, not all) plus attention over the context
    by kind: all of it in a full layer, no more than the window in a sliding
    one."""
    attention, router, expert, head = _per_row(config)
    full, window = layers_by_kind(config)
    params = (full + window) * (
        attention + router + config["num_experts_per_tok"] * expert) + head
    seen = full * context + window * min(context, config["sliding_window"])
    return rows * (2 * params + paged_attention_flops(config, seen))


def expert_flops(config, rows):
    """FLOPs of one layer's expert product over ``rows`` rows: the routed
    (row, expert) pairs only."""
    return 2 * rows * config["num_experts_per_tok"] * _per_row(config)[2]


def expert_bytes(config, rows):
    """Bytes one layer's expert product must move: the weights of every
    expert that draws a row, once (under an even routing an expert draws none
    of ``rows`` x top-k pairs with probability (1 - top_k / E) ** rows: 1.4%
    at a step's 32 rows, 63.1 of 64), the routed pairs in and out."""
    E, k = config["num_experts"], config["num_experts_per_tok"]
    drawn = E * (1.0 - (1.0 - k / E) ** rows)
    moved = rows * k * (2 * config["hidden_size"]
                        + 2 * config["moe_intermediate_size"])
    return _width(config) * (drawn * _per_row(config)[2] + moved)


def paged_attention_op(config, lanes):
    """The paged-attention kernel's name in a trace's breakdown at a step of
    ``lanes`` lanes: context part, running maximum and denominator; both
    kinds of layer print it (the 32 query heads of a lane's one row are the
    32 rows of one matmul over the whole 512-wide row)."""
    rows = config["num_attention_heads"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return ("custom-call[tpu_custom_call] -> (f32[{0},1,{1},{2}], "
            "f32[{0},1,{1},1], f32[{0},1,{1},1])").format(lanes, rows, kv)


def prefill_attention_flops(config, rows):
    """FLOPs the attention of one layer of a prefill requires over a prompt
    of ``rows`` rows, at the **mean over a prefill's layers**: both kinds
    print one kernel name, so a call is set against the mean of the causal
    requirement (half the square) and the banded one (``rows`` x window,
    less the band's corner), weighted by the layers of each kind."""
    w = min(rows, config["sliding_window"])
    each = 4 * config["num_attention_heads"] * config["head_dim"]
    full, window = layers_by_kind(config)
    causal = rows * rows / 2
    banded = rows * w - w * w / 2
    return each * (full * causal + window * banded) / (full + window)


def prefill_attention_bytes(config, rows):
    """Bytes it must move: queries in and result out for every query head,
    keys and values in for every KV head."""
    heads = 2 * config["num_attention_heads"] \
        + 2 * config["num_key_value_heads"]
    return heads * rows * config["head_dim"] * _width(config)


def prefill_attention_ops(config, rungs):
    """``{the flash kernel's name at a rung: the rung's rows}`` for the
    prefill rungs the kernel takes (the shorter ones go the dense way):
    result and log-sum-exp of every query head."""
    N, D = config["num_attention_heads"], config["head_dim"]
    dtype = {"bfloat16": "bf16", "float32": "f32"}[config["dtype"]]
    label = "custom-call[tpu_custom_call] -> ({}[{},{},{}], f32[{},{},128])"
    return {label.format(dtype, N, S, D, N, S): S for S in rungs
            if S >= 512}


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
ATTENTION = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")


@contextlib.contextmanager
def _float8_products():
    """The reference's products with both operands through float8 while a
    control's function is traced: a row of activations under its own scale,
    a matrix (one expert's, inside the scan over experts) under one."""
    mm = reference._mm
    reference._mm = lambda x, w: mm(_float8(x, (-1,)), _float8(w, (-2, -1)))
    try:
        yield
    finally:
        reference._mm = mm


@functools.lru_cache(maxsize=None)
def _device_functions(dims_json, stand_in):
    """The reference a layer at a time, jitted: weights arrive as the program
    holds them and are upcast to float32 inside, attention's at once, the
    experts' one expert at a time, attention's scores a head and a block of
    query rows at a time, so that the check fits beside the weights and the
    pool. ``stand_in``: None, ``"float8"`` (the control) or ``"all_full"``
    (no layer keeps a window)."""
    import jax
    import jax.numpy as jnp
    dims = json.loads(dims_json)
    if stand_in == "all_full":
        dims["sliding_window"] = 1 << 30
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    products = _float8_products if stand_in == "float8" \
        else contextlib.nullcontext
    eps = dims["rms_norm_eps"]

    def layer(x, p, positions, kind):
        rows = min(ROW_BLOCK, len(x))
        with products():
            h = x + reference.attention(
                reference.rms_norm(x, f32(p["ln1"]), eps),
                f32({k: p[k] for k in ATTENTION}), positions, kind, dims,
                head_block=HEAD_BLOCK, row_block=rows)
            g = reference.rms_norm(h, f32(p["ln2"]), eps)
            weights = reference.route(g, f32(p["router"]), dims)

            def one(acc, ew):
                wg, wu, wd, w = ew
                return acc + w[:, None] * reference.gated(
                    g, *f32((wg, wu, wd))), None

            y, _ = jax.lax.scan(one, jnp.zeros_like(g), (
                p["w_gate"], p["w_up"], p["w_down"], weights.T))
        return h + y

    def logits_at(x, final_norm, head, first, count):
        rows = jax.lax.dynamic_slice_in_dim(x, first, count)
        with products():
            return reference.head_logits(
                rows, f32({"final_norm": final_norm, "head": head}), dims)

    return (jax.jit(lambda w, t: w[t].astype(jnp.float32)),
            jax.jit(layer, static_argnums=3),
            jax.jit(logits_at, static_argnums=4))


def _functions(config, stand_in=None):
    prose = ("assumed", "rehearse", "deployment", "reduced", "source",
             "published")
    return _device_functions(json.dumps(
        {k: v for k, v in config.items() if k not in prose},
        sort_keys=True), stand_in)


def served_logits(cell, config, weights, prompt, tokens, stand_in=None):
    """The reference's logits (T, V), as numpy, at the T positions that chose
    ``tokens``: one causal forward over prompt + tokens, right-padded to the
    cell's ``max_seq_len`` rows (padding reaches no checked position; every
    request then runs the one compiled shape), the head at the served
    positions only."""
    embed, layer, logits_at = _functions(config, stand_in)
    seq = list(prompt) + list(tokens)
    rows = cell["max_seq_len"]
    ids = onp.zeros(rows, onp.int32)
    ids[:len(seq)] = seq
    positions = onp.arange(rows, dtype=onp.int32)
    x = embed(weights["embed"], ids)
    for p, kind in zip(weights["layers"], config["layer_types"]):
        x = layer(x, p, positions, kind)
    # every answer's logits as one shape: the budget's largest, cut after
    count = min(rows, cell["output_len"]["max"])
    first = min(len(prompt) - 1, rows - count)
    out = onp.asarray(logits_at(x, weights["final_norm"], weights["head"],
                                first, count))
    at = len(prompt) - 1 - first
    return out[at:at + len(tokens)]


def check_requests(bench, lm, done, vocab):
    """(ok, what was seen) for the finished requests of a run. Of all of
    them: ids in range, the budget met. Of a seeded sample, the longest
    finished sequence always in it, teacher-forced: the reference runs once
    over prompt + served tokens and reads, at every served position,

    - ``worst_logit_deficit``: how far the served token's logit lies under
      the reference's best at its position, the worst position (a wrong
      token);
    - ``tokens_off_best_pct``: the share of served tokens that are not the
      reference's first choice at their position (the precision: a routed
      layer makes single rows jump in any precision but the reference's, so
      the worst row says little about it and the share says much)."""
    cell, config = bench.cell, bench.config
    bad_ids = sum(not 0 <= t < vocab for r in done for t in r.tokens)
    unmet = sum(len(r.tokens) != r.budget for r in done)
    weights = _weights(lm)
    picks = _sample(bench, done)
    deficits, lower, altered, unwindowed = [], [], [], []
    for i in picks:
        r = done[i]
        served = onp.asarray(r.tokens)
        logits = served_logits(cell, config, weights, r.prompt, r.tokens)
        deficits.append(_read(logits, served))
        if not CONTROL:
            continue
        low = served_logits(cell, config, weights, r.prompt, r.tokens,
                            "float8")
        lower.append(_read(logits, low.argmax(-1)))
        altered.append(_read(logits, (served + 7) % vocab)[::5])
        full = served_logits(cell, config, weights, r.prompt, r.tokens,
                             "all_full")
        unwindowed.append(_read(logits, full.argmax(-1)))
    deficits = onp.concatenate(deficits)
    numbers = lambda d: {
        "worst_logit_deficit": float(d.max()),
        "tokens_off_best_pct": 100.0 * float((d > 0).mean())}
    limits = {"worst_logit_deficit": cell["logit_tolerance"],
              "tokens_off_best_pct": cell["off_best_limit_pct"]}
    program = numbers(deficits)
    compared = {name: {"value": program[name], "limit": limit}
                for name, limit in limits.items()}
    compared.update({
        "ids_out_of_range": {"value": bad_ids, "limit": 0},
        "budgets_unmet": {"value": unmet, "limit": 0}})
    ok = all(row["value"] <= row["limit"] for row in compared.values())
    seen = {"checked_requests": len(picks),
            "checked_tokens": int(len(deficits)),
            "checked_rows": [len(done[i].prompt) + len(done[i].tokens)
                             for i in picks],
            # no limit: what the worst row's jump is made of
            "median_deficit_off_best": float(onp.median(
                deficits[deficits > 0])) if (deficits > 0).any() else 0.0,
            "compared": compared}
    if CONTROL:
        low, alt, full = (onp.concatenate(d)
                          for d in (lower, altered, unwindowed))
        over = lambda d: sorted(n for n, limit in limits.items()
                                if numbers(d)[n] > limit)
        bench.say({"control": {
            "float8": {**numbers(low), "over": over(low)},
            # the run's number would be the largest; the smallest and the
            # share over the limit say how many single wrong tokens fail it
            "every_fifth_token_altered": {
                "worst_logit_deficit": float(alt.max()),
                "median_logit_deficit": float(onp.median(alt)),
                "smallest_logit_deficit": float(alt.min()),
                "over_the_limit_pct": 100.0 * float(
                    (alt > limits["worst_logit_deficit"]).mean()),
                "tokens": int(len(alt)),
                "over": ["worst_logit_deficit"] * bool(
                    alt.max() > limits["worst_logit_deficit"])},
            # does what was served tell a wrong window? On the chip at the
            # cell's size it does (every served token is another than the one
            # a model without windows puts first), so it has to fail too
            "every_layer_full": {**numbers(full), "over": over(full)},
            "limits": limits,
            "comes_out_not_correct": bool(over(low) and over(full))}})
    return ok, seen


def main(argv=None):
    from .. import harness
    # run as ``python -m`` this file is ``__main__``; the driver reaches the
    # family by its own name, and that module's flag is the one it reads
    from . import mellum2 as family
    family.CONTROL = True
    try:
        return harness.main(argv)
    finally:
        family.CONTROL = False


if __name__ == "__main__":
    sys.exit(main())
