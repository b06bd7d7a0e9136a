"""The quickest proof that mxnet_tpu still starts on the chip.

Drives the three main paths once through the entry points a user would call,
at the full width of the models the benchmark and the serving stack are built
for, on ONE TPU chip in ONE process:

  1. train/resnet50   ParallelTrainStep as a training job builds it (b32, bf16)
  2. train/bert_base  the same for BERT-base pretraining, at seq 128 (dense
                      attention, the path of record) and at seq 512 (where the
                      Pallas flash-attention kernel must be in the program)
  3. serve/resnet50   InferenceServer + ModelEndpoint under ``mx.tpu(0)``
  4. decode           DecodeEndpoint + PagedKVPool + DecodeScheduler over a
                      TransformerLM at BERT-base widths
  5. restart          phase 3's endpoint rebuilt from MXNET_EXEC_CACHE_DIR

Every phase prints one JSON line (seconds, of which compile, what it checked)
and raises when a check fails, which ends the script with a non-zero code.
The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Without a TPU the script exits non-zero before any phase and prints no result:
there is no CPU mode. The phases are functions of their sizes and of the
context they run on, so a rehearsal can import this file and call them tiny on
the CPU; nothing on the command line does that.

``--chips 4`` runs instead, on a four-chip host, only what exists across chips:
the BERT-base step on a dp=2 x tp=2 mesh against the same step on one chip,
and the vocab-sharded embedding's all_to_all step against a dense reference.

Weights are random, from ``--seed``. This is a smoke, not a benchmark: the
seconds it prints are there so the next reader knows what a cold start costs.
"""
import argparse
import json
import os
import shutil
import sys
import threading
import time

import numpy as onp

_ROOT = os.path.dirname(os.path.abspath(__file__))
# fixed and inside the checkout; emptied at start so that phase 3 compiles
# cold and phase 5 can only be served by what phase 3 stored
_EXEC_CACHE_DIR = os.path.join(_ROOT, ".chip_smoke_exec_cache")

# a bf16 step against its float32 twin: bf16 keeps 8 bits of mantissa, and a
# loss is a mean over thousands of such terms
_BF16_RTOL = 5e-2


class _CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching from
    its persistent cache), and how often that cache answered, summed from
    JAX's own monitoring events."""

    _SECONDS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
                "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
                "/jax/core/compile/backend_compile_duration": "backend_s"}
    _COUNTS = {"/jax/compilation_cache/compile_requests_use_cache":
               "cache_requests",
               "/jax/compilation_cache/cache_hits": "cache_hits"}

    def __init__(self):
        import jax.monitoring
        self._lock = threading.Lock()
        self._sums = dict.fromkeys(
            list(self._SECONDS.values()) + list(self._COUNTS.values()), 0.0)
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, secs, **_: self._add(self._SECONDS.get(event), secs))
        jax.monitoring.register_event_listener(
            lambda event, **_: self._add(self._COUNTS.get(event), 1))

    def _add(self, key, amount):
        if key is not None:
            with self._lock:
                self._sums[key] += amount

    def read(self):
        with self._lock:
            return dict(self._sums)


def _run_phase(name, clock, fn, *args, **kwargs):
    """Run one phase and print its line. No except clause: a failed check
    propagates and ends the script. A phase returns what it checked, or
    that and something a later phase needs, which is handed back."""
    t0, c0 = time.perf_counter(), clock.read()
    out = fn(*args, **kwargs)
    checked, handed_on = out if isinstance(out, tuple) else (out, None)
    spent = {k: v - c0[k] for k, v in clock.read().items()}
    print(json.dumps({
        "phase": name, "seconds": round(time.perf_counter() - t0, 2),
        "compile_s": round(sum(v for k, v in spent.items()
                               if k.endswith("_s")), 2),
        "compile": {k: round(v, 2) if k.endswith("_s") else int(v)
                    for k, v in spent.items()},
        "checked": checked}), flush=True)
    return handed_on


def _close(got, want, rtol, what):
    if not abs(got - want) <= rtol * abs(want):
        raise AssertionError(f"{what}: {got} vs reference {want} "
                             f"(rtol {rtol})")


def _on_device(arrays, dev, what):
    import jax
    for a in jax.tree_util.tree_leaves(arrays):
        if set(a.devices()) != {dev}:
            raise AssertionError(f"{what}: array on {a.devices()}, "
                                 f"expected {dev}")


def _cpu_mesh():
    import jax
    from mxnet_tpu import parallel
    return parallel.make_mesh({"dp": 1}, devices=jax.devices("cpu")[:1])


def _compiled_step_text(step, x, y, *extras):
    """Optimised-HLO text of a ParallelTrainStep's one-step program for this
    batch, lowered with the arguments step() itself passes. The step has run,
    so with the compile cache on this costs a cache read."""
    import jax.numpy as jnp
    import mxnet_tpu as mx
    placed = step.place_batch(x, y, *extras)
    train = [step._params[i] for i in step._trainable_idx]
    aux = [step._params[i] for i in step._aux_idx]
    rates = jnp.zeros((len(train),), jnp.float32)
    return step._step_fn.lower(
        train, aux, step._opt_states, placed[0], placed[1],
        tuple(placed[2:]), mx.random.take_key(), rates, rates,
        jnp.float32(1)).compile().as_text()


def _check_training(step, ref_loss, batch, steps, k, dev):
    """The checks both training phases share: ``steps`` calls of step() and
    one step_n of ``k`` on one batch; losses finite and falling, first loss
    near the float32 reference, a parameter moved, state on ``dev``."""
    import jax
    before = onp.asarray(jax.device_get(step.params[step._trainable_idx[-1]]))
    losses = [float(step.step(*batch).asscalar()) for _ in range(steps)]
    stacked = jax.tree_util.tree_map(lambda a: onp.stack([a] * k), batch)
    losses_n = [float(v) for v in step.step_n(*stacked).asnumpy()]
    if not onp.all(onp.isfinite(losses + losses_n)):
        raise AssertionError(f"non-finite loss: {losses} {losses_n}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    _close(losses[0], ref_loss, _BF16_RTOL, "first-step loss vs CPU float32")
    after = onp.asarray(jax.device_get(step.params[step._trainable_idx[-1]]))
    if onp.array_equal(before, after):
        raise AssertionError("no parameter changed")
    _on_device((step.params, step._opt_states), dev, "carried train state")
    return {"losses": [round(v, 4) for v in losses],
            "step_n_losses": [round(v, 4) for v in losses_n],
            "cpu_f32_first_loss": round(ref_loss, 4)}


# ---------------------------------------------------------------------------
# phase 1
# ---------------------------------------------------------------------------
def train_resnet(dev, seed, model="resnet50_v1", classes=1000, img=224,
                 batch=32, steps=3, k=4):
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(seed)
    onp.random.seed(seed)
    net = vision.get_model(model, classes=classes)
    net.initialize(mx.init.Xavier())
    net(mx.nd.array(onp.zeros((1, 3, img, img), "float32")))  # shapes
    rng = onp.random.default_rng(seed)
    x = rng.random((batch, 3, img, img), dtype="float32")
    y = rng.integers(0, classes, (batch,)).astype("float32")

    def build(mesh, dtype):
        return parallel.ParallelTrainStep(
            net, gloss.SoftmaxCrossEntropyLoss(),
            mx.optimizer.SGD(learning_rate=0.05, momentum=0.9), mesh,
            compute_dtype=dtype)

    # both steps copy the block's (untouched) parameters: same weights
    ref_loss = float(build(_cpu_mesh(), None).step(x, y).asscalar())
    step = build(parallel.make_mesh({"dp": 1}), "bfloat16")
    return _check_training(step, ref_loss, (x, y), steps, k, dev)


# ---------------------------------------------------------------------------
# phase 2
# ---------------------------------------------------------------------------
def _bert_pretrain(seed, batch, seq, vocab, **widths):
    """BERT pretraining model and one seeded batch. Returns (model, x, y,
    extras)."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import bert

    mx.random.seed(seed)
    onp.random.seed(seed)
    backbone = bert.BERTModel(vocab_size=vocab, max_length=seq, **widths)
    model = bert.BERTForPretraining(backbone, vocab_size=vocab)
    model.initialize(mx.init.Normal(0.02))
    n_pred = max(1, int(seq * 0.15))
    rng = onp.random.RandomState(seed)
    toks = rng.randint(0, vocab, (batch, seq)).astype("int32")
    tt = onp.zeros((batch, seq), "int32")
    positions = onp.sort(
        rng.rand(batch, seq).argsort(-1)[..., :n_pred], -1).astype("int32")
    mlm_lab = rng.randint(0, vocab, (batch, n_pred)).astype("int32")
    nsp_lab = rng.randint(0, 2, (batch,)).astype("int32")
    return model, toks, (mlm_lab, nsp_lab), (tt, positions)


def _bert_step(model, mesh, dtype, masked=False):
    """The training step. ``masked`` gives the reference's variant:
    an all-valid mask sends attention down the masked XLA composite, a
    second implementation that never enters flash_attention."""
    import mxnet_tpu as mx
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo import bert

    class _PretrainStep(HybridBlock):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def forward(self, tokens, token_types, positions):
            valid = nd.ones_like(tokens) if masked else None
            return self.inner(tokens, token_types, valid, positions)

    return parallel.ParallelTrainStep(
        _PretrainStep(model), bert.BERTPretrainingLoss(),
        mx.optimizer.Adam(learning_rate=1e-4), mesh,
        compute_dtype=dtype, extra_specs=(P("dp"), P("dp")))


_BERT_BASE = dict(num_layers=12, units=768, hidden_size=3072, num_heads=12)


def train_bert(dev, seed, shapes=((64, 128), (8, 512)), kernel_from=512,
               vocab=30522, steps=3, k=4, widths=None):
    """``shapes`` is (batch, seq) pairs; from seq ``kernel_from`` on, the
    compiled step must hold the Pallas kernel — on the TPU, the only place
    where it is compiled and not interpreted."""
    from mxnet_tpu import parallel
    out = {}
    for batch, seq in shapes:
        model, x, y, extras = _bert_pretrain(seed, batch, seq, vocab,
                                             **(widths or _BERT_BASE))
        ref_loss = float(_bert_step(model, _cpu_mesh(), None, masked=True)
                         .step(x, y, *extras).asscalar())
        step = _bert_step(model, parallel.make_mesh({"dp": 1}), "bfloat16")
        row = _check_training(step, ref_loss, (x, y) + extras, steps, k, dev)
        row["tpu_custom_calls"] = _compiled_step_text(
            step, x, y, *extras).count("tpu_custom_call")
        if dev.platform == "tpu" and \
                (row["tpu_custom_calls"] > 0) != (seq >= kernel_from):
            raise AssertionError(
                f"seq {seq}: {row['tpu_custom_calls']} tpu_custom_call(s) in "
                f"the compiled step; the Pallas kernel belongs there from "
                f"seq {kernel_from} and not below")
        out[f"b{batch}_s{seq}"] = row
    return out


# ---------------------------------------------------------------------------
# phase 3 and phase 5
# ---------------------------------------------------------------------------
_SERVE_NAME = "smoke_resnet"


def _serve_endpoint(net, img, max_batch):
    from mxnet_tpu import serving
    serving.unregister(_SERVE_NAME)
    return serving.ModelEndpoint(_SERVE_NAME, net, input_shapes=(3, img, img),
                                 dtype="bfloat16", max_batch_size=max_batch)


def serve_resnet(ctx, seed, model="resnet50_v1", classes=1000, img=224,
                 max_batch=8, clients=8, requests_per_client=4):
    """Returns (checked, what restart_serving needs)."""
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.gluon.model_zoo import vision

    dev = ctx.jax_device()
    with ctx:                       # the public way onto the chip
        mx.random.seed(seed)
        onp.random.seed(seed)
        net = vision.get_model(model, classes=classes)
        net.initialize(mx.init.Xavier())
        net.cast("bfloat16")
        net(mx.nd.zeros((1, 3, img, img), dtype="bfloat16"))
        ep = _serve_endpoint(net, img, max_batch)
        server = serving.InferenceServer(batch_timeout_ms=2.0)
        server.register(ep)         # warms every bucket
        server.start()
    if ep._device_label() != f"{dev.platform}:{dev.id}":
        raise AssertionError(f"endpoint labelled {ep._device_label()!r}, "
                             f"context is {dev}")
    if ep._donate_inputs() != (dev.platform == "tpu"):
        raise AssertionError("input donation is off on the TPU")

    # requests are windows of one pool of frames, so one direct forward of
    # the pool is the reference for all of them
    frames = onp.random.default_rng(seed).random(
        (max_batch, 3, img, img), dtype="float32")
    row_counts = [r for r in (1, 2, 3, 5, max_batch) if r <= max_batch]
    answers, errors = {}, []

    def client(ci):
        for j in range(requests_per_client):
            rows = row_counts[(ci + j) % len(row_counts)]
            start = (ci + j) % (max_batch - rows + 1)
            try:
                out = server.predict(_SERVE_NAME, frames[start:start + rows],
                                     timeout=300)
                answers[(ci, j)] = (start, rows, out.asnumpy())
            except Exception as e:   # re-raised below, on the main thread
                errors.append(e)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    alive = [t for t in threads if t.is_alive()]
    single = server.predict(_SERVE_NAME, frames[0], timeout=300).asnumpy()
    server.stop(drain=True)
    if errors:
        raise errors[0]
    if alive or len(answers) != clients * requests_per_client:
        raise AssertionError(f"{len(answers)} of "
                             f"{clients * requests_per_client} requests "
                             f"answered, {len(alive)} clients stuck")
    compiles = ep.stats.counters["compiles"]
    if compiles != len(ep.buckets):
        raise AssertionError(f"{compiles} compiles for {len(ep.buckets)} "
                             "buckets: traffic compiled")

    with ctx:
        net.hybridize()
        direct = net(mx.nd.array(frames, dtype="bfloat16")) \
            .asnumpy().astype("float32")
    atol = _BF16_RTOL * float(onp.abs(direct).max())
    for start, rows, out in list(answers.values()) + [(0, 1, single[None])]:
        want = direct[start:start + rows]
        got = out.astype("float32")
        if got.shape != want.shape or not onp.all(onp.isfinite(got)) or \
                not onp.allclose(got, want, rtol=_BF16_RTOL, atol=atol):
            raise AssertionError(
                f"served rows {start}:{start + rows} differ from the direct "
                f"forward by {onp.abs(got - want).max()} (atol {atol})")
    # the same leading frames through each bucket's executable (the server
    # is stopped; which bucket a request met under traffic is chance)
    bitwise = {b: bool(onp.array_equal(
        _probe_answer(ep, frames[:b]).astype("float32"), direct[:b]))
        for b in ep.buckets}
    checked = {"requests": len(answers) + 1, "buckets": list(ep.buckets),
               "compiles": compiles, "device": ep._device_label(),
               "donation": ep._donate_inputs(),
               # information, not a check: the README's bitwise claim
               "bitwise_vs_direct_by_bucket": bitwise}
    return checked, {"net": net, "img": img, "max_batch": max_batch,
                     "probe": frames, "answer": _probe_answer(ep, frames)}


def _probe_answer(ep, frames):
    """The answer for ``frames`` straight from the endpoint (no server),
    through the bucket that fits them."""
    outs, _ = ep.run_batch((frames.astype(ep.np_dtypes[0]),), len(frames))
    return onp.asarray(outs[0])


def restart_serving(ctx, served):
    """A second server and endpoint under phase 3's name, in a process whose
    executable cache phase 3 filled: nothing may compile."""
    from mxnet_tpu import serving, telemetry
    from mxnet_tpu.cache import executable_cache as xcache

    def misses():
        family = telemetry.REGISTRY.get("mxtpu_exec_cache_misses_total")
        return {labels[0]: child.value for labels, child in family._series()}

    before = telemetry.compile_ledger.summary()
    misses0, hits0 = misses(), xcache.stats()["hits"]
    with ctx:
        ep = _serve_endpoint(served["net"], served["img"], served["max_batch"])
        server = serving.InferenceServer(batch_timeout_ms=2.0)
        server.register(ep)
        server.start()
    first = server.predict(_SERVE_NAME, served["probe"][0], timeout=300)
    server.stop(drain=True)
    after = telemetry.compile_ledger.summary()
    loads = after["compiles"] - before["compiles"]
    fresh = loads - (after["cache_hits"] - before["cache_hits"])
    missed = {r: n - misses0.get(r, 0) for r, n in misses().items()
              if n != misses0.get(r, 0)}
    if loads != len(ep.buckets) or fresh != 0 or missed:
        raise AssertionError(
            f"restart: {loads} executables obtained for {len(ep.buckets)} "
            f"buckets, {fresh} of them compiled; executable-cache misses by "
            f"reason {missed}; store {xcache.stats()}")
    if not onp.all(onp.isfinite(first.asnumpy().astype("float32"))):
        raise AssertionError("restart: non-finite first answer")
    if not onp.array_equal(_probe_answer(ep, served["probe"]),
                           served["answer"]):
        raise AssertionError("restart: the deserialised executable does not "
                             "give phase 3's answer")
    serving.unregister(_SERVE_NAME)
    return {"fresh_compiles": fresh, "cache_hits": loads,
            "exec_cache_hits": xcache.stats()["hits"] - hits0,
            "error_misses": 0, "answers_bitwise_equal": True}


# ---------------------------------------------------------------------------
# phase 4
# ---------------------------------------------------------------------------
def decode(ctx, seed, prompt_lens=(5, 20, 70, 200), max_new=16, vocab=30522,
           max_length=512, prefill_buckets=(32, 128, 512), widths=None,
           init_std=0.05):
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.gluon.model_zoo.bert import TransformerLM

    dev = ctx.jax_device()
    name = "smoke_lm"
    rng = onp.random.RandomState(seed)
    prompts = [[int(t) for t in rng.randint(1, vocab, n)]
               for n in prompt_lens]
    with ctx:
        mx.random.seed(seed)
        onp.random.seed(seed)
        lm = TransformerLM(vocab_size=vocab, max_length=max_length,
                           **(widths or _BERT_BASE))
        # wider than a trained model's init, as in the tier-1 oracle. At 32
        # units that makes greedy argmax hang on the history; twelve random
        # layers at 768 settle each sequence on one token of its own
        lm.initialize(mx.init.Normal(init_std))
        eng = serving.DecodeEndpoint(name, lm, max_seq_len=max_length,
                                     max_batch_size=len(prompts),
                                     prefill_buckets=prefill_buckets)
        server = serving.InferenceServer()
        server.register_generator(eng)      # warms every executable
    warm = eng.stats.snapshot()["counters"]["compiles"]
    _on_device((eng.pool.k_pool, eng.pool.v_pool, eng._param_datas()), dev,
               "decode pool and weights")

    def serial(prompt, sid):
        """One sequence at a time through the SAME executables, on this
        thread, before the scheduler's own thread exists: the oracle."""
        eng.pool.reserve(sid, len(prompt) + max_new)
        table = eng.pool.table(sid)
        toks = [eng.prefill(prompt, table)]
        for pos in range(len(prompt), len(prompt) + max_new - 1):
            toks.append(eng.decode_step([(toks[-1], pos, table)])[0])
        eng.pool.free(sid)
        return toks

    oracle = [serial(p, 900000 + i) for i, p in enumerate(prompts)]
    # sequences that all gave the same tokens could be mixed up, row for row
    # or page for page, and still compare equal below
    if len(set(map(tuple, oracle))) < 2:
        raise AssertionError(f"every sequence decodes alike: {oracle}")
    # information: does a prompt changed in all but its last token decode
    # differently? Twelve random layers may well ignore their context
    other = [t % (vocab - 1) + 1 for t in prompts[0][:-1]] + prompts[0][-1:]
    context_matters = serial(other, 900100) != oracle[0]

    server.start()
    streams = [server.generate(name, p, max_new_tokens=max_new)
               for p in prompts]
    results = [s.result(timeout=600) for s in streams]
    server.stop(drain=True)
    if results != oracle:
        raise AssertionError(f"batched decode {results} differs from serial "
                             f"greedy decode {oracle}")
    # and the tokens are the model's own: greedy decode of the shortest
    # sequence by a whole causal forward per token (no cache, no pages).
    # Padding on the right cannot reach a causal position to its left
    pad = min(b for b in eng.prefill_buckets
              if b >= len(prompts[0]) + max_new)
    toks = list(prompts[0])
    with ctx:
        lm.hybridize()
        for _ in range(max_new):
            x = onp.zeros((1, pad), "int32")
            x[0, :len(toks)] = toks
            logits = lm(mx.nd.array(x, dtype="int32")).asnumpy()
            toks.append(int(logits[0, len(toks) - 1].argmax()))
    if toks[len(prompts[0]):] != oracle[0]:
        raise AssertionError(f"decode through the cache {oracle[0]} differs "
                             f"from whole forwards {toks[len(prompts[0]):]}")
    compiles = eng.stats.snapshot()["counters"]["compiles"]
    if compiles != warm:
        raise AssertionError(f"decode compiled after warm-up: {warm} -> "
                             f"{compiles}")
    _on_device((eng.pool.k_pool, eng.pool.v_pool), dev, "decode pool")
    return {"sequences": len(prompts), "new_tokens_each": max_new,
            "executables": warm, "compiles_after_warmup": compiles - warm,
            "equals_serial_greedy": True, "equals_whole_forward": True,
            "distinct_sequences": len(set(map(tuple, oracle))),
            "distinct_tokens_per_sequence": [len(set(t)) for t in oracle],
            "earlier_prompt_tokens_change_output": context_matters,
            "pool_mib": round(2 * eng.pool.k_pool.nbytes / 2 ** 20, 1),
            "pool_device": f"{dev.platform}:{dev.id}"}


# ---------------------------------------------------------------------------
# --chips 4: what exists only across chips
# ---------------------------------------------------------------------------
def multichip_bert(seed, batch=64, seq=128, vocab=30522, steps=3, widths=None):
    """The BERT step on dp=2 x tp=2 against the same seeded step on one chip
    of the same host."""
    import jax
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.model_zoo import bert

    devs = jax.devices()
    model, x, y, extras = _bert_pretrain(seed, batch, seq, vocab,
                                         **(widths or _BERT_BASE))
    one = _bert_step(model, parallel.make_mesh({"dp": 1}, devices=devs[:1]),
                     "bfloat16")
    losses_one = [float(one.step(x, y, *extras).asscalar())
                  for _ in range(steps)]
    # annotate only now: the one-chip mesh has no 'tp' axis to shard over
    annotated = bert.shard_for_tensor_parallel(model)
    step = _bert_step(model, parallel.make_mesh({"dp": 2, "tp": 2}),
                      "bfloat16")
    losses = [float(step.step(x, y, *extras).asscalar())
              for _ in range(steps)]
    for a, b in zip(losses, losses_one):
        _close(a, b, 2e-2, "dp2 x tp2 loss vs one chip")
    if not onp.all(onp.isfinite(losses)) or not losses[-1] < losses[0]:
        raise AssertionError(f"dp2 x tp2 losses {losses}")
    partitioned = 0
    for arr, sharding in zip(step.params, step._param_shardings):
        if "tp" not in jax.tree_util.tree_leaves(tuple(sharding.spec)):
            continue
        shards = arr.addressable_shards
        if not all(onp.prod(s.data.shape) < onp.prod(arr.shape)
                   for s in shards):
            raise AssertionError(f"{sharding.spec}: a shard is as large as "
                                 f"the whole {arr.shape}")
        if len({s.device for s in shards}) != 4:
            raise AssertionError(f"{sharding.spec}: shards on "
                                 f"{[s.device for s in shards]}")
        partitioned += 1
    if partitioned != annotated:
        raise AssertionError(f"{annotated} parameters annotated, "
                             f"{partitioned} partitioned")
    return {"losses_dp2_tp2": [round(v, 4) for v in losses],
            "losses_one_chip": [round(v, 4) for v in losses_one],
            "tp_params_partitioned": partitioned,
            "shards_on_distinct_devices": 4}


def multichip_embedding(seed, vocab=1 << 20, dim=64, batch=4096, fields=8,
                        dense_in=13, lr=0.05):
    """Vocab-sharded lookup and one all_to_all train step on tp=4 against a
    dense table on one device."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import parallel
    from mxnet_tpu.embedding import (DLRMTrainStep, ShardedEmbedding,
                                     synthetic_dlrm_batches)
    from mxnet_tpu.embedding.workload import (bce_loss, dlrm_forward,
                                              init_mlp_params)

    mesh = parallel.make_mesh({"tp": 4})
    w0 = onp.random.RandomState(seed).normal(
        0, 0.01, (vocab, dim)).astype("float32")
    emb = ShardedEmbedding(vocab, dim, mesh, axis="tp", weight=w0)
    dense, idx, y = synthetic_dlrm_batches(1, batch, dense_in, fields, vocab,
                                           seed=seed + 1)[0]
    if not onp.array_equal(onp.asarray(emb.lookup(idx)), w0[idx]):
        raise AssertionError("sharded lookup differs from the dense gather")
    shards = emb.weight.addressable_shards
    if len({s.device for s in shards}) != 4 or \
            any(s.data.shape[0] * 4 != emb.padded_vocab for s in shards):
        raise AssertionError(f"table shards {[s.data.shape for s in shards]}")

    step = DLRMTrainStep(emb, dense_in, fields, lr=lr, seed=seed,
                         mode="sharded")
    staged = step.stage((dense, idx, y))
    text = step._step.lower(emb.weight, step.mlp, staged["dense"],
                            staged["idx"], staged["y"]).compile().as_text()
    n_a2a = text.count(" all-to-all")
    if not n_a2a:
        raise AssertionError("no all-to-all in the compiled embedding step")
    loss = step(staged)

    # the reference: the whole table on one device, plain gather and add
    dev = jax.devices()[0]
    mlp = {k: jax.device_put(v, dev) for k, v in init_mlp_params(
        dense_in, fields, dim, seed=seed).items()}

    @jax.jit
    def ref_step(tbl, mlp, dense, idx, y):
        def fwd(mlp, rows):
            return bce_loss(jnp, dlrm_forward(jnp, mlp, dense, rows), y)
        ref_loss, (_, g_rows) = jax.value_and_grad(
            fwd, argnums=(0, 1))(mlp, tbl[idx])
        return tbl.at[idx].add(-lr * g_rows), ref_loss

    ref_tbl, ref_loss = ref_step(jax.device_put(w0, dev), mlp,
                                 jax.device_put(dense, dev),
                                 jax.device_put(idx, dev),
                                 jax.device_put(y, dev))
    _close(loss, float(ref_loss), 1e-4, "sharded DLRM loss vs dense")
    got, want = emb.dense_weight(), onp.asarray(ref_tbl)
    if not onp.allclose(got, want, rtol=1e-4, atol=1e-7):
        raise AssertionError(f"updated table differs from the dense "
                             f"reference by {onp.abs(got - want).max()}")
    touched = int((got != w0).any(axis=1).sum())
    if not touched:
        raise AssertionError("the step changed no row of the table")
    return {"table": [vocab, dim], "table_mib": round(w0.nbytes / 2 ** 20),
            "batch": batch, "lookup_bitwise": True, "all_to_all": n_a2a,
            "loss": round(loss, 6), "dense_loss": round(float(ref_loss), 6),
            "rows_updated": touched}


# ---------------------------------------------------------------------------
def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip paths, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import cache, native
    from mxnet_tpu.base import MXNetError

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise MXNetError(f"chip_smoke needs a TPU; JAX found {devs}")
    if len(devs) != args.chips:
        raise MXNetError(f"--chips {args.chips}, but JAX found {len(devs)} "
                         "device(s)")
    clock = _CompileClock()
    print(json.dumps({"start": True, "jax": jax.__version__,
                      "compile_cache": cache.enable_compile_cache(),
                      "native_available": native.available(),
                      "native_build_error": native.build_error()}),
          flush=True)

    if args.chips == 4:
        _run_phase("multichip/bert_base_dp2_tp2", clock, multichip_bert,
                   args.seed)
        _run_phase("multichip/embedding_tp4", clock, multichip_embedding,
                   args.seed)
    else:
        shutil.rmtree(_EXEC_CACHE_DIR, ignore_errors=True)
        mx.config.set("MXNET_EXEC_CACHE_DIR", _EXEC_CACHE_DIR)
        ctx, dev = mx.tpu(0), devs[0]
        _run_phase("train/resnet50", clock, train_resnet, dev, args.seed)
        _run_phase("train/bert_base", clock, train_bert, dev, args.seed)
        served = _run_phase("serve/resnet50", clock, serve_resnet, ctx,
                            args.seed)
        _run_phase("decode", clock, decode, ctx, args.seed)
        _run_phase("restart", clock, restart_serving, ctx, served)

    stats = devs[0].memory_stats() or {}
    print(json.dumps({"peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                      "bytes_limit": stats.get("bytes_limit")}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
