"""SDAR-MoE (``model_type`` ``sdar_moe``): the program's ``MoEDecoderLM`` at a
configuration's sizes, what a forward costs, and the check of what block
diffusion served against the plain reference (``reference/sdar_moe.py``).

    python -m chipbench.models.sdar_moe --workload <cell> --seed <n> ...

runs the cell as ``python -m chipbench`` does and, after the check, reads the
same requests once more with stand-ins in the program's place, each of which
has to come out as not correct by the limit that is there for it:

- the control: the reference computed in float8 (e4m3), the nearest precision
  below the bfloat16 the configuration states for weights and activations
  alike: both operands of every projection, of the router, of every expert's
  three products and of the head go through float8 (a matrix under one scale,
  a row of activations under its own; accumulation, softmax and norms stay
  float32, as in the program). At every (block, step) state the served
  streams went through it places the tokens and rows *it* puts first with
  the confidences it holds for them, and the same numbers are read under the
  reference: what a program computing in that precision would show
  (``median_confidence_error_pct``);
- a step that places the masked rows the reference is *least* sure of
  (``states_out_of_order_pct``);
- every fifth placed token altered (``worst_logit_deficit``).

It also reads, and holds to nothing, the reference with float8 in the
experts' matrices alone and float32 everywhere else: that computation lies
as close to the reference as the bfloat16 program does (PERF.md section 2),
so no number read from what was served tells the two apart.
"""
import contextlib
import functools
import sys

import numpy as onp

from ..harness import seed32
from ..reference import sdar_moe as reference

CONTROL = False          # set by this module's own command


def build_lm(config, seed):
    """The model on the current context, every weight drawn on the device in
    the configuration's dtype from ``seed``: N(0, init_std), the head
    ``head_init_scale`` times as wide (``assumed`` in the file says why)."""
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
        rms_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        block_length=config["block_length"],
        mask_token_id=config["mask_token_id"], dtype=config["dtype"],
        prefix="lm_")
    # served, not trained: no gradient buffer beside each of 4.36 B weights
    lm.collect_params().setattr("grad_req", "null")
    lm.initialize(mx.init.DeviceNormal(
        config["init_std"], seed=seed,
        scales={"head_weight": config["head_init_scale"]}))
    return lm


# ---------------------------------------------------------------------------
# what a forward requires (hand-counted for one layer in the tests)
# ---------------------------------------------------------------------------
def _per_row(config):
    """Parameters one row multiplies: (in a layer outside the experts, in
    one expert, in the head)."""
    H, D = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * D, config["num_key_value_heads"] * D
    attention = H * q + 2 * H * kv + q * H
    router = H * config["num_experts"]
    expert = 3 * H * config["moe_intermediate_size"]
    return attention + router, expert, H * config["vocab_size"]


def forward_flops(config, rows, context):
    """FLOPs a forward of ``rows`` rows requires, each attending to
    ``context`` positions: 2 x the parameters a row multiplies (its
    ``num_experts_per_tok`` experts, not all) plus scores and weighted
    values."""
    dense, expert, head = _per_row(config)
    layers = config["num_hidden_layers"]
    attend = 4 * config["num_attention_heads"] * config["head_dim"] * context
    return rows * (layers * (2 * (dense + config["num_experts_per_tok"]
                                  * expert) + attend) + 2 * head)


def expert_flops(config, rows):
    """FLOPs of one layer's expert product over ``rows`` rows: the routed
    (row, expert) pairs only."""
    return 2 * rows * config["num_experts_per_tok"] * _per_row(config)[1]


def _width(config):
    return {"bfloat16": 2, "float32": 4}[config["dtype"]]


def expert_bytes(config, rows):
    """Bytes one layer's expert product must move: every held expert's
    weights once (at 8 experts a row, 256 rows leave an expert without a row
    once in 10 million), the routed rows in and the result out."""
    pairs = rows * config["num_experts_per_tok"]
    moved = pairs * (2 * config["hidden_size"]
                     + 2 * config["moe_intermediate_size"])
    return _width(config) * (config["num_experts"] * _per_row(config)[1]
                             + moved)


def expert_ops(config, rows):
    """The device kernels that compute one layer's expert product over
    ``rows`` rows, as a trace's breakdown names them, each with its calls a
    layer: the Pallas grouped matmul the program calls on the chip
    (``megablox.gmm``, ``ops/nn.py:_grouped_matmul``) is a custom call whose
    result is (routed pairs, width) in float32, the gate's and the up's of
    the expert width, the down's of the hidden size
    (``tests/chipbench/fixtures/trace_sdar_step_v5e.json`` pins them)."""
    pairs = rows * config["num_experts_per_tok"]
    label = "custom-call[tpu_custom_call] -> f32[{},{}]".format
    return {label(pairs, config["moe_intermediate_size"]): 2,
            label(pairs, config["hidden_size"]): 1}


def forward_bytes(config, rows, context):
    """Bytes a forward of ``rows`` rows must read: every weight once, the
    rows' embeddings, and each row's group's live context (keys and values
    of ``context`` positions a layer, shared by the ``block_length`` rows of
    a lane)."""
    dense, expert, head = _per_row(config)
    layers = config["num_hidden_layers"]
    weights = layers * (dense + config["num_experts"] * expert) + head
    kv = 2 * config["num_key_value_heads"] * config["head_dim"]
    lanes = rows / config["block_length"]
    return _width(config) * (weights + rows * config["hidden_size"]
                             + layers * lanes * context * kv)


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
def _weights(lm):
    """The system's arrays under the reference's names (a layer's dict at a
    time goes to the device function, which upcasts it)."""
    raw = lambda p: p.data().data
    return {"embed": raw(lm.embed_weight), "final_norm": raw(lm.final_norm),
            "head": raw(lm.head_weight),
            "layers": [{k: raw(v) for k, v in layer.items()}
                       for layer in lm.layers]}


def _float8(a, axes):
    """``a`` rounded to float8 (e4m3: 4 exponent and 3 mantissa bits) under
    one scale over ``axes``, in float32. ``reduce_precision`` and no cast to
    the type: the TPU's compiler widens a float8 it has no unit for, and the
    cast there and back rounds nothing (read on the chip: a control that
    differed from the reference in no token)."""
    import jax
    import jax.numpy as jnp
    scale = jnp.abs(a).max(axes, keepdims=True) / 240.0    # e4m3's largest
    scale = jnp.where(scale > 0, scale, 1.0)
    return jax.lax.reduce_precision(a / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


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
def _device_functions(dims_items, lower):
    """The reference a layer at a time, jitted: weights arrive as the program
    holds them and are upcast to float32 inside, so that one layer's float32
    copy is all the chip holds beside the program. ``lower``: None, ``"all"``
    (the control: every product's operands through float8) or ``"experts"``
    (the experts' matrices alone, an expert's under one scale)."""
    import jax
    import jax.numpy as jnp
    dims = dict(dims_items)
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    products = _float8_products if lower == "all" else contextlib.nullcontext

    def layer(x, p, positions, copy, valid):
        mask = reference.unrolled_mask(positions, copy, valid,
                                       dims["block_length"])
        p = f32(p)
        if lower == "experts":
            p = {**p, **{k: _float8(p[k], (-2, -1))
                         for k in ("w_gate", "w_up", "w_down")}}
        with products():
            return reference.layer(x, p, positions, mask, dims)

    def logits_at(x, final_norm, head, rows):
        with products():
            logits = reference.head_logits(
                x[rows], f32({"final_norm": final_norm, "head": head}), dims)
        return logits.at[:, dims["mask_token_id"]].set(-jnp.inf)

    def read(logits, tokens):
        """(best logit, its id, its confidence, the logit of ``tokens``) per
        row, the mask token left out."""
        ids, conf = reference.candidates(logits, dims["mask_token_id"])
        return (logits.max(-1), ids, conf,
                logits[jnp.arange(len(tokens)), tokens])

    return (jax.jit(lambda w, t: w[t].astype(jnp.float32)), jax.jit(layer),
            jax.jit(logits_at), jax.jit(read))


def _functions(config, lower=None):
    return _device_functions(
        tuple(sorted((k, v) for k, v in config.items()
                     if isinstance(v, (int, float, bool)))), lower)


def _state_logits(config, weights, laid, lower=None):
    """One forward over an unrolled request (``reference.unroll``): (its
    noisy rows, their logits (R, V) on the device); every request of a cell
    has as many, so nothing compiles twice."""
    embed, layer, logits_at, _ = _functions(config, lower)
    rows = onp.flatnonzero(laid["copy"] >= 0)
    x = embed(weights["embed"], laid["tokens"])
    for p in weights["layers"]:
        x = layer(x, p, laid["positions"], laid["copy"], laid["valid"])
    return rows, logits_at(x, weights["final_norm"], weights["head"], rows)


def _read(config, logits, tokens):
    return [onp.asarray(a) for a in _functions(config)[3](logits, tokens)]


def _states_read(laid, rows, top, conf, at, sure, placed_of, per_step):
    """Over one request's (block, step) states, as lists: ``logit``, the
    logit deficit of every placed row (``top`` less ``at``, per noisy row);
    ``error``, how far the confidence it was placed with (``sure``) lies from
    the reference's at that row, as a share of the reference's;
    ``confidence``, for every state that placed some masked rows and passed
    over others, the reference's confidence at the surest row passed over
    less that at the least sure row placed; and ``off_schedule``, how many
    states placed another number of rows than the schedule's.
    ``placed_of(state, its rows' indices)`` names the rows of the state that
    were placed at it."""
    out = {"logit": [], "error": [], "confidence": [], "off_schedule": 0}
    for st in laid["states"]:
        idx = [r - rows[0] for r in st["rows"]]
        placed = placed_of(st, idx)
        masked = [i for i, m in enumerate(st["masked"]) if m]
        if len(placed) != min(per_step, len(masked)):
            out["off_schedule"] += 1
        passed = [i for i in masked if i not in placed]
        for k in (idx[i] for i in placed):
            out["logit"].append(float(top[k] - at[k]))
            out["error"].append(float(abs(sure[k] - conf[k]) / conf[k]))
        if placed and passed:
            out["confidence"].append(float(
                max(conf[idx[i]] for i in passed)
                - min(conf[idx[i]] for i in placed)))
    return out


def _numbers(cell, read):
    """The numbers the cell limits, from :func:`_states_read`'s lists."""
    out_of_order = sum(d > cell["confidence_margin"]
                       for d in read["confidence"])
    return {"worst_logit_deficit": max([0.0, *read["logit"]]),
            "median_confidence_error_pct":
                100.0 * float(onp.median(read["error"] or [0.0])),
            "states_out_of_order_pct":
                100.0 * out_of_order / max(len(read["confidence"]), 1)}


def _sample(bench, done):
    """Indices into ``done`` of a sample drawn from the seed, the longest
    finished sequence always in it."""
    rng = onp.random.default_rng(seed32(bench.seed, 2))
    picks = rng.choice(len(done), min(bench.cell["checked_requests"],
                                      len(done)), replace=False)
    longest = max(range(len(done)),
                  key=lambda i: len(done[i].prompt) + len(done[i].tokens))
    if longest not in picks:
        picks[0] = longest
    return [int(i) for i in picks]


def check_requests(bench, lm, done, vocab):
    """(ok, what was seen) for the finished requests of a run. Of all of them:
    ids in range and never the mask token, ``max_new_tokens`` met, a step and
    a confidence recorded for every token. Of the sample, from each stream's
    record of the step at which each token was placed, the reference
    recomputes every (block, step) state in one forward
    (:func:`reference.unroll`) and reads

    - ``worst_logit_deficit``: how far a placed token's logit lies under the
      reference's best at its row (a wrong token);
    - ``median_confidence_error_pct``: how far the confidence a token was
      placed with lies from the reference's at that row, as a share of it, at
      the median placed row (the precision: routed experts make the worst
      row a matter of which rows change an expert, in bfloat16 and float8
      alike, and the median row changes none);
    - ``states_out_of_order_pct``: the share of states in which the
      reference is surer of a masked row that was passed over than of a row
      that was placed, by more than ``confidence_margin`` (the schedule: rows
      that hold the same mask token differ by their rotation alone, so
      equality cannot be asked of every state);
    - whether each state placed as many rows as the schedule says."""
    cell, config = bench.cell, bench.config
    mask_id, L = config["mask_token_id"], config["block_length"]
    steps = cell["generate"]["denoising_steps"]
    per_step = L // steps
    bad_ids = sum(not 0 <= t < vocab or t == mask_id
                  for r in done for t in r.tokens)
    unmet = sum(not len(r.tokens) == r.budget == len(r.stream.steps)
                == len(r.stream.confidences) for r in done)
    weights = _weights(lm)
    # a ragged tail can spread a budget over one block more
    sizes = dict(clean_rows=cell["max_seq_len"],
                 noisy_rows=(-(-max(r.budget for r in done) // L) + 1) * L)
    # the program's lists and each stand-in's
    read = {who: {"logit": [], "error": [], "confidence": [],
                  "off_schedule": 0} for who in
            ("program", "float8", "float8_expert_weights",
             "lowest_confidence_first")}
    altered = []
    other_tokens = {"float8": 0, "float8_expert_weights": 0}
    states = 0
    picks = _sample(bench, done)

    def add(who, lists):
        for name, seen in lists.items():
            read[who][name] += seen

    for i in picks:
        r = done[i]
        laid = reference.unroll(r.prompt, r.tokens, r.stream.steps, config,
                                steps, **sizes)
        rows, logits = _state_logits(config, weights, laid)
        # per noisy row: the token served there and what it was placed with
        served = onp.zeros(len(rows), onp.int32)
        sure = onp.ones(len(rows), onp.float32)
        for st in laid["states"]:
            here = onp.asarray(st["rows"]) - rows[0]
            served[here] = st["tokens"]
            sure[here] = [r.stream.confidences[a] if p else 1.0
                          for a, p in zip(st["answer"], st["placed"])]
        top, ids, conf, at = _read(config, logits, served)
        add("program", _states_read(
            laid, rows, top, conf, at, sure,
            lambda st, idx: [i for i, p in enumerate(st["placed"]) if p],
            per_step))
        states += len(laid["states"])
        if not CONTROL:
            continue
        # each lower precision's own candidates and confidences at the same
        # states, read under the reference's logits
        for who, lower in (("float8", "all"),
                           ("float8_expert_weights", "experts")):
            _, low_ids, low_conf, _ = _read(config, _state_logits(
                config, weights, laid, lower)[1], served)
            at_low = _read(config, logits, low_ids)[3]
            add(who, _states_read(
                laid, rows, top, conf, at_low, low_conf,
                lambda st, idx: reference.place(low_conf[idx], st["masked"],
                                                per_step), per_step))
            other_tokens[who] += int((low_ids != ids).sum())
        # the reference's own tokens, the rows it is least sure of first
        add("lowest_confidence_first", _states_read(
            laid, rows, top, conf, top, conf,
            lambda st, idx: reference.place(-conf[idx], st["masked"],
                                            per_step), per_step))
        # every fifth served token altered, under the reference
        at_other = _read(config, logits, (served + 7) % mask_id)[3]
        placed_rows = [st["rows"][i] - rows[0] for st in laid["states"]
                       for i, p in enumerate(st["placed"]) if p]
        altered += [float(top[k] - at_other[k]) for k in placed_rows[::5]]
    limits = {
        "worst_logit_deficit": cell["logit_tolerance"],
        "median_confidence_error_pct": cell["confidence_error_limit_pct"],
        "states_out_of_order_pct": cell["out_of_order_limit_pct"]}
    numbers = {who: _numbers(cell, lists) for who, lists in read.items()}
    compared = {name: {"value": numbers["program"][name], "limit": limit}
                for name, limit in limits.items()}
    compared.update({
        "states_off_schedule": {"value": read["program"]["off_schedule"],
                                "limit": 0},
        "ids_out_of_range_or_mask": {"value": bad_ids, "limit": 0},
        "budgets_unmet": {"value": unmet, "limit": 0}})
    ok = all(row["value"] <= row["limit"] for row in compared.values())
    seen = {"checked_requests": len(picks), "checked_states": states,
            "placed_rows": len(read["program"]["logit"]),
            "confidence_margin": cell["confidence_margin"],
            # no limit: ruled by single rows (PERF.md section 2)
            "worst_confidence_deficit": max(
                [0.0, *read["program"]["confidence"]]),
            "rows_under_the_best": sum(
                d > 0 for d in read["program"]["logit"]),
            "denoising_steps": steps, "compared": compared}
    if CONTROL:
        over = lambda who: sorted(name for name, limit in limits.items()
                                  if numbers[who][name] > limit)
        lower = {who: {
            **numbers[who],
            "worst_confidence_deficit": max([0.0, *read[who]["confidence"]]),
            "rows_under_the_best": sum(d > 0 for d in read[who]["logit"]),
            "rows_with_another_first_token": other_tokens[who],
            "over": over(who)} for who in other_tokens}
        bench.say({"control": {
            **lower,
            "lowest_confidence_first": {
                **numbers["lowest_confidence_first"],
                "worst_confidence_deficit": max(
                    [0.0, *read["lowest_confidence_first"]["confidence"]]),
                "over": over("lowest_confidence_first")},
            "every_fifth_token_altered": {
                "smallest_logit_deficit": min(altered),
                "tokens": len(altered),
                "over": ["worst_logit_deficit"] * (
                    min(altered) > limits["worst_logit_deficit"])},
            "placed_rows": len(read["float8"]["logit"]),
            "limits": limits,
            "comes_out_not_correct": bool(over("float8"))}})
    return ok, seen


def main(argv=None):
    from .. import harness
    # run as ``python -m`` this file is ``__main__``; the driver reaches the
    # family by its own name, and that module's flag is the one it reads
    from . import sdar_moe as family
    family.CONTROL = True
    try:
        return harness.main(argv)
    finally:
        family.CONTROL = False


if __name__ == "__main__":
    sys.exit(main())
