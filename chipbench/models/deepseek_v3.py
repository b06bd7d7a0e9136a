"""DeepSeek-V3 (``model_type`` ``deepseek_v3``): the program's
``MLADecoderLM`` at a configuration's sizes, what its forward, its kernels and
its experts cost, and the check of what the decode path served against the
plain reference (``reference/deepseek_v3.py``).

    python -m chipbench.models.deepseek_v3 --workload <cell> --seed <n> ...

runs the cell as ``python -m chipbench`` does and, after the check, reads the
same requests once more with stand-ins in the program's place, each of which
has to come out as not correct by the limit that is there for it:

- the control: the reference with both operands of every product through
  float8 (e4m3), the nearest precision below the bfloat16 the configuration
  states: at each served position it takes the token *it* puts first, read
  under the reference (``tokens_off_best_pct``);
- every fifth served token altered (``worst_logit_deficit``: the worst of
  them, which is what a run so served would read; the smallest and the share
  of them over the limit are printed beside it).
"""
import contextlib
import functools
import json
import sys

import numpy as onp

from ..harness import seed32
from ..reference import deepseek_v3 as reference

CONTROL = False          # set by this module's own command
HEAD_BLOCK = 4           # heads whose (T, T) float32 scores the check holds
FFN_CHUNK = 2048         # columns of the dense MLP the check upcasts at once


def router_width(config):
    """Experts the router scores: the published count where the file holds a
    share of them."""
    return config.get("published", {}).get("n_routed_experts",
                                           config["n_routed_experts"])


def build_lm(config, seed):
    """The model on the current context, every weight drawn on the device in
    the configuration's dtype from ``seed``: N(0, init_std), the router's
    correction bias N(0, router_bias_std)."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.mla_lm import MLADecoderLM

    lm = MLADecoderLM(
        num_layers=config["num_hidden_layers"], units=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
        nope_dim=config["qk_nope_head_dim"],
        rope_dim=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
        dense_layers=config["first_k_dense_replace"],
        dense_hidden=config["intermediate_size"],
        expert_hidden=config["moe_intermediate_size"],
        num_experts=router_width(config),
        experts_per_token=config["num_experts_per_tok"],
        shared_experts=config["n_shared_experts"], n_group=config["n_group"],
        topk_group=config["topk_group"],
        routed_scale=config["routed_scaling_factor"],
        norm_topk=config["norm_topk_prob"], vocab_size=config["vocab_size"],
        rms_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        rope_scaling=config["rope_scaling"],
        held_experts=config["held_experts"], dtype=config["dtype"],
        prefix="lm_")
    # served, not trained: no gradient buffer beside each of 4.57 B weights
    lm.collect_params().setattr("grad_req", "null")
    lm.initialize(mx.init.DeviceNormal(
        config["init_std"], seed=seed,
        scales={"router_bias": config["router_bias_std"]
                / config["init_std"]}))
    return lm


# ---------------------------------------------------------------------------
# what a forward requires (hand-counted in the tests)
# ---------------------------------------------------------------------------
def _per_row(config):
    """Parameters one row multiplies: (attention of a layer, the dense
    layer's MLP, the router, one expert, the head)."""
    H, N = config["hidden_size"], config["num_attention_heads"]
    q, kv = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    attention = (H * q + q * N * (dn + dr) + H * (kv + dr)
                 + kv * N * (dn + dv) + N * dv * H)
    return (attention, 3 * H * config["intermediate_size"],
            H * router_width(config), 3 * H * config["moe_intermediate_size"],
            H * config["vocab_size"])


def _held_share(config):
    return config["n_routed_experts"] / router_width(config)


def _layers(config):
    dense = config["first_k_dense_replace"]
    return dense, config["num_hidden_layers"] - dense


def latent_row(config):
    """Numbers a cached position holds a layer: the latent and its rotary
    key."""
    return config["kv_lora_rank"] + config["qk_rope_head_dim"]


def latent_attention_flops(config, positions):
    """FLOPs one layer's absorbed attention requires over ``positions``
    cached positions (summed over lanes): every head scores the row (kv_rank
    + rope) and weighs its first kv_rank columns."""
    return 2 * positions * config["num_attention_heads"] * (
        latent_row(config) + config["kv_lora_rank"])


def latent_attention_bytes(config, positions):
    """Bytes one layer's absorbed attention must read: each cached position's
    row **once**, keys and values in one (the stored row's padding is not
    required reading)."""
    return positions * latent_row(config) * _width(config)


def prefill_attention_flops(config, rows):
    """FLOPs one layer's plain causal attention requires over a prompt of
    ``rows`` rows: half the square, keys of nope + rope, values of v."""
    wide = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
            + config["v_head_dim"])
    return config["num_attention_heads"] * rows * rows / 2 * wide * 2


def prefill_attention_bytes(config, rows):
    """Bytes it must move: queries and keys in, values in, result out."""
    wide = 2 * (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
                + config["v_head_dim"])
    return config["num_attention_heads"] * rows * wide * _width(config)


def forward_flops(config, rows, context):
    """FLOPs a forward of ``rows`` rows requires on this chip, each attending
    to ``context`` cached positions: 2 x the parameters a row multiplies (of
    its ``num_experts_per_tok`` routed experts the share held here, the
    shared expert whole) plus the absorbed attention."""
    attention, mlp, router, expert, head = _per_row(config)
    dense, sparse = _layers(config)
    routed = config["num_experts_per_tok"] * _held_share(config)
    params = (dense + sparse) * attention + dense * mlp + sparse * (
        router + (config["n_shared_experts"] + routed) * expert) + head
    return rows * (2 * params + (dense + sparse)
                   * latent_attention_flops(config, context))


def _width(config):
    return {"bfloat16": 2, "float32": 4}[config["dtype"]]


def _pairs_here(config, rows):
    return rows * config["num_experts_per_tok"] * _held_share(config)


def expert_flops(config, rows):
    """FLOPs of one layer's routed product over ``rows`` rows: the (row,
    expert) pairs routed to the experts held here, a sixteenth-of-256 share
    of all in expectation."""
    return 2 * _pairs_here(config, rows) * _per_row(config)[3]


def expert_bytes(config, rows):
    """Bytes one layer's routed product must move: the weights of every held
    expert that draws a row, once, the pairs routed here in and out. An
    expert without a row is not read; under an even routing an expert draws
    none of ``rows`` x top-k pairs with probability (1 - top_k / E) ** rows,
    13% at 64 rows, which leaves 13.9 of 16 (on the chip a step's 64 rows
    drew 13.4, PERF.md, PR 32: hidden states are correlated, so this count
    lies 3% over what was read)."""
    E = router_width(config)
    drawn = config["n_routed_experts"] * (
        1.0 - (1.0 - config["num_experts_per_tok"] / E) ** rows)
    moved = _pairs_here(config, rows) * (
        2 * config["hidden_size"] + 2 * config["moe_intermediate_size"])
    return _width(config) * (drawn * _per_row(config)[3] + moved)


def _pass_pairs(config, rows):
    """Pairs one pass of the grouped products holds at ``rows`` rows: all
    rows x top-k where that is one pass, else the program's block."""
    from mxnet_tpu.ops.nn import _PAIR_BLOCK
    return min(rows * config["num_experts_per_tok"], _PAIR_BLOCK)


def expert_ops(config, rows):
    """The device kernels of one layer's routed product in a step of ``rows``
    rows, as a trace's breakdown names them, each with its calls a layer: the
    Pallas grouped matmul (``megablox.gmm`` through ``ops/nn.py``) is a
    custom call whose result is (pairs of the pass, width) in float32."""
    label = "custom-call[tpu_custom_call] -> f32[{},{}]".format
    pairs = _pass_pairs(config, rows)
    return {label(pairs, config["moe_intermediate_size"]): 2,
            label(pairs, config["hidden_size"]): 1}


def latent_attention_op(config, lanes):
    """The paged-attention kernel's name in a trace's breakdown, in its
    latent mode at a step of ``lanes`` lanes: context part, running maximum
    and denominator of every head on the one shared row."""
    N = config["num_attention_heads"]
    return ("custom-call[tpu_custom_call] -> (f32[{0},1,{1},{2}], "
            "f32[{0},1,{1},1], f32[{0},1,{1},1])").format(
                lanes, N, config["kv_lora_rank"])


def prefill_attention_ops(config, rungs):
    """``{the flash kernel's name at a rung: the rung's rows}`` for the
    prefill rungs the kernel takes (the shorter ones go the dense way):
    result and log-sum-exp of every head."""
    N, dv = config["num_attention_heads"], config["v_head_dim"]
    dtype = {"bfloat16": "bf16", "float32": "f32"}[config["dtype"]]
    label = "custom-call[tpu_custom_call] -> ({}[{},{},{}], f32[{},{},128])"
    return {label.format(dtype, N, S, dv, N, S): S for S in rungs
            if S >= 512}


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
ATTENTION = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo")


def _weights(lm):
    """The system's arrays under the reference's names (a layer's dict at a
    time goes to the device function, which upcasts it piece by piece)."""
    raw = lambda p: p.data().data
    return {"embed": raw(lm.embed_weight), "final_norm": raw(lm.final_norm),
            "head": raw(lm.head_weight),
            "layers": [{k: raw(v) for k, v in layer.items()}
                       for layer in lm.layers]}


@contextlib.contextmanager
def _float8_products():
    """The reference's products with both operands through float8 while a
    control's function is traced: a row of activations under its own scale,
    a matrix under one (``sdar_moe._float8``: ``reduce_precision``, since the
    TPU's compiler widens a float8 it has no unit for)."""
    from .sdar_moe import _float8
    mm = reference._mm
    reference._mm = lambda x, w: mm(_float8(x, (-1,)), _float8(w, (-2, -1)))
    try:
        yield
    finally:
        reference._mm = mm


@functools.lru_cache(maxsize=None)
def _device_functions(dims_json, float8):
    """The reference a layer at a time, jitted: weights arrive as the program
    holds them and are upcast to float32 inside, attention's at once (0.75
    GB), the dense MLP's ``FFN_CHUNK`` columns at a time and the experts' one
    expert at a time (the gated MLP is a sum over its columns), so that the
    check fits beside the weights and the pool. ``float8``: the control."""
    import jax
    import jax.numpy as jnp
    dims = json.loads(dims_json)
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    products = _float8_products if float8 else contextlib.nullcontext
    eps = dims["rms_norm_eps"]

    def gated_sum(h, w_gate, w_up, w_down, weights):
        """Sum over the leading axis of weight x gated(h, ...), one at a
        time, each upcast inside."""
        def one(acc, ew):
            wg, wu, wd, w = ew
            return acc + w[:, None] * reference.gated(h, *f32((wg, wu, wd))), \
                None
        return jax.lax.scan(one, jnp.zeros_like(h),
                            (w_gate, w_up, w_down, weights))[0]

    def layer(x, p, positions):
        with products():
            h = x + reference.attention(
                reference.rms_norm(x, f32(p["ln1"]), eps),
                f32({k: p[k] for k in ATTENTION}), positions, dims,
                head_block=min(HEAD_BLOCK, dims["num_attention_heads"]))
            g = reference.rms_norm(h, f32(p["ln2"]), eps)
            # the dense MLP (or the shared expert) by columns
            H, wide = p["mlp_gate"].shape
            n = max(1, wide // FFN_CHUNK)
            cols = lambda w: w.reshape(H, n, wide // n).transpose(1, 0, 2)
            y = gated_sum(g, cols(p["mlp_gate"]), cols(p["mlp_up"]),
                          p["mlp_down"].reshape(n, wide // n, H),
                          jnp.ones((n, len(g)), jnp.float32))
            if "router" in p:
                first, count = dims["held_experts"]
                weights = reference.route(g, f32({
                    "router": p["router"], "router_bias": p["router_bias"]}),
                    dims)[:, first:first + count]
                y = y + gated_sum(g, p["w_gate"], p["w_up"], p["w_down"],
                                  weights.T)
        return h + y

    def logits_at(x, final_norm, head, first, count):
        rows = jax.lax.dynamic_slice_in_dim(x, first, count)
        with products():
            return reference.head_logits(
                rows, f32({"final_norm": final_norm, "head": head}), dims)

    return (jax.jit(lambda w, t: w[t].astype(jnp.float32)), jax.jit(layer),
            jax.jit(logits_at, static_argnums=4))


def _functions(config, float8=False):
    prose = ("assumed", "rehearse", "deployment", "reduced", "source")
    return _device_functions(json.dumps(
        {k: v for k, v in config.items() if k not in prose},
        sort_keys=True), float8)


def served_logits(cell, config, weights, prompt, tokens, float8=False):
    """The reference's logits (T, V), as numpy, at the T positions that chose
    ``tokens``: one causal forward over prompt + tokens, right-padded to the
    cell's ``max_seq_len`` rows (padding reaches no checked position; every
    request then runs the one compiled shape)."""
    embed, layer, logits_at = _functions(config, float8)
    seq = list(prompt) + list(tokens)
    rows = cell["max_seq_len"]
    ids = onp.zeros(rows, onp.int32)
    ids[:len(seq)] = seq
    positions = onp.arange(rows, dtype=onp.int32)
    x = embed(weights["embed"], ids)
    for p in weights["layers"]:
        x = layer(x, p, positions)
    # every answer's logits as one shape: the budget's largest, cut after
    count = min(rows, cell["output_len"]["max"])
    first = min(len(prompt) - 1, rows - count)
    out = onp.asarray(logits_at(x, weights["final_norm"], weights["head"],
                                first, count))
    at = len(prompt) - 1 - first
    return out[at:at + len(tokens)]


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


def _read(logits, tokens):
    """Per served position: how far ``tokens``' logit lies under the row's
    best."""
    return logits.max(-1) - logits[onp.arange(len(tokens)), tokens]


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
    deficits, lower, altered = [], [], []
    for i in picks:
        r = done[i]
        served = onp.asarray(r.tokens)
        logits = served_logits(cell, config, weights, r.prompt, r.tokens)
        deficits.append(_read(logits, served))
        if not CONTROL:
            continue
        low = served_logits(cell, config, weights, r.prompt, r.tokens,
                            float8=True)
        lower.append(_read(logits, low.argmax(-1)))
        other = (served + 7) % vocab
        altered.append(_read(logits, other)[::5])
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
        low, alt = onp.concatenate(lower), onp.concatenate(altered)
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
            "limits": limits,
            "comes_out_not_correct": bool(over(low))}})
    return ok, seen


def main(argv=None):
    from .. import harness
    # run as ``python -m`` this file is ``__main__``; the driver reaches the
    # family by its own name, and that module's flag is the one it reads
    from . import deepseek_v3 as family
    family.CONTROL = True
    try:
        return harness.main(argv)
    finally:
        family.CONTROL = False


if __name__ == "__main__":
    sys.exit(main())
