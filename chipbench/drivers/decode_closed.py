"""Closed-loop decode traffic: N callers, each sending its next request when
its last one finished, through InferenceServer.generate (DecodeEndpoint +
PagedKVPool + DecodeScheduler). The loop is benchmark/serving_loadgen.py's
``_run_decode``; sizes and seeds come from the cell file and ``--seed``.

Every seed gets the same set of (prompt length, output length) pairs, drawn
once from the cell's ``pool_seed``, in another order and with other token ids,
so that seeds change the order of the work and not the work.

Times are the client's: a token's time is when the stream handed it to the
request's ``on_token`` callback. Tokens count by that time, time to first
token by requests submitted inside the window, and what is in flight when the
window ends is drained outside it.

A cell's optional ``generate`` group goes to ``server.generate`` as keyword
arguments beside ``max_new_tokens`` and ``on_token``. The answers' check is
the family's ``check_requests(bench, lm, good, vocab)`` where it has one (a
generation step that is not one causal token brings its own; each finished
request still holds its ``stream``, with whatever the system recorded of its
steps), else ``_check_requests`` below.
"""
import threading
import time

import numpy as onp

from ..harness import seed32, span
from ..stats import median, percentile

# 64 callers on 64 slots keep the system at capacity, where the tokens per
# second completed are the end-to-end metric; the tails swing by 5-16% from
# run to run there (PERF.md, PR 23) and are per-layer metrics
END_TO_END = {"decode_tokens_per_s": "tokens/s"}
KIND = "decode"              # which per-layer readers apply (their ``KINDS``)
NAME = "chipbench_lm"


def lengths(spec, n, rng):
    """n lengths from a lognormal with the given median and sigma, clipped."""
    draw = rng.lognormal(onp.log(spec["median"]), spec["sigma"], n)
    return onp.clip(onp.rint(draw), spec["min"], spec["max"]).astype(int)


def make_requests(cell, vocab, seed):
    """[(prompt ids, output budget)...]: the cell's fixed pool of sizes in
    this seed's order, with this seed's token ids."""
    sizes = onp.random.default_rng(cell["pool_seed"])
    n = int(cell["request_pool"])
    pairs = list(zip(lengths(cell["prompt_len"], n, sizes),
                     lengths(cell["output_len"], n, sizes)))
    rng = onp.random.default_rng(seed)
    order = rng.permutation(n)
    return [([int(t) for t in rng.integers(0, vocab, pairs[i][0])],
             int(pairs[i][1])) for i in order]


class _Request:
    __slots__ = ("prompt", "budget", "submitted", "stamps", "stream",
                 "tokens", "error")

    def __init__(self, prompt, budget):
        self.prompt, self.budget = prompt, budget
        self.submitted = None
        self.stamps = []
        self.stream = None
        self.tokens = None
        self.error = None


class _Clients:
    """The callers. Each takes the next request of the shared sequence, sends
    it and waits for its whole answer."""

    def __init__(self, server, requests, n, timeout, generate=None):
        self._server, self._requests = server, requests
        self._timeout = timeout
        self._generate = dict(generate or {})
        self._lock = threading.Lock()
        self._next = 0
        self.done = []
        self.turned_over = set()
        self._stop = threading.Event()
        self._threads = [threading.Thread(target=self._client, args=(i,),
                                          daemon=True) for i in range(n)]

    def start(self):
        for t in self._threads:
            t.start()

    def stop_and_drain(self):
        self._stop.set()
        for t in self._threads:
            t.join()

    def _take(self):
        with self._lock:
            prompt, budget = self._requests[self._next % len(self._requests)]
            self._next += 1
        return _Request(prompt, budget)

    def _client(self, ci):
        clock = time.perf_counter
        while not self._stop.is_set():
            req = self._take()
            stamps = req.stamps
            req.submitted = clock()
            try:
                with span("submit"):
                    req.stream = self._server.generate(
                        NAME, req.prompt, max_new_tokens=req.budget,
                        on_token=lambda tok: stamps.append(clock()),
                        **self._generate)
                req.tokens = req.stream.result(timeout=self._timeout)
            except Exception as e:       # counted as failed, never dropped
                req.error = e
            with self._lock:
                self.done.append(req)
                self.turned_over.add(ci)


CHECK_ROWS = 4               # rows per reference forward: (4, 512, V) logits


def sampled_rows(bench, done):
    """(picks, tokens): the indices into ``done`` of a sample drawn from the
    seed, with the longest finished sequence in it, and their prompt + answer
    ids as rows right-padded to ``max_seq_len`` (causal, so padding reaches
    no checked position)."""
    cell = bench.cell
    rng = onp.random.default_rng(seed32(bench.seed, 2))
    picks = rng.choice(len(done), min(cell["checked_requests"], len(done)),
                       replace=False)
    longest = max(range(len(done)),
                  key=lambda i: len(done[i].prompt) + len(done[i].tokens))
    if longest not in picks:
        picks[0] = longest
    toks = onp.zeros((len(picks), cell["max_seq_len"]), onp.int32)
    for row, i in enumerate(picks):
        seq = done[i].prompt + done[i].tokens
        toks[row, :len(seq)] = seq
    return picks, toks


def logits_in_blocks(logits_of, toks):
    """``logits_of(rows)`` over ``toks`` in blocks of CHECK_ROWS rows (the
    last one padded, so every call has one shape), each fetched to the host:
    a block of logits at a time is all the device holds."""
    for at in range(0, len(toks), CHECK_ROWS):
        block = toks[at:at + CHECK_ROWS]
        pad = onp.zeros((CHECK_ROWS - len(block), toks.shape[1]), toks.dtype)
        yield at, onp.asarray(logits_of(onp.concatenate([block, pad])))


def served_logits(done, picks, toks, logits_of):
    """(request, logits (T, V)) for each sampled request with an answer: the
    rows of ``logits_of`` that chose its T served tokens, teacher-forced."""
    for at, logits in logits_in_blocks(logits_of, toks):
        for row, i in enumerate(picks[at:at + CHECK_ROWS]):
            r = done[i]
            if r.tokens:
                first = len(r.prompt) - 1
                yield r, logits[row, first:first + len(r.tokens)]


def _check_requests(bench, lm, family, done, vocab):
    """Ids in range, none longer than its budget, and for a seeded sample the
    teacher-forced check: under the reference's full causal forward over
    prompt + output, each generated token's logit is within the cell's
    tolerance of its row's maximum (token equality would trip on ties that
    rounding breaks either way). Returns (ok, what was seen)."""
    cell = bench.cell
    ok_ids = all(0 <= t < vocab for r in done for t in r.tokens)
    ok_len = all(len(r.tokens) <= r.budget for r in done)
    picks, toks = sampled_rows(bench, done)
    worst = 0.0
    for r, here in served_logits(
            done, picks, toks,
            lambda rows: family.reference_logits(lm, bench.config, rows)):
        served = here[onp.arange(len(r.tokens)), r.tokens]
        worst = max(worst, float((here.max(-1) - served).max()))
    seen = {"ids_in_range": ok_ids, "within_budget": ok_len,
            "checked_requests": len(picks),
            "checked_tokens": int(sum(len(done[i].tokens) for i in picks)),
            "worst_logit_deficit": worst,
            "logit_tolerance": cell["logit_tolerance"]}
    return ok_ids and ok_len and worst <= cell["logit_tolerance"], seen


def run(bench):
    from mxnet_tpu import serving

    cell, config = bench.cell, bench.config
    family = bench.family()
    vocab = config["vocab_size"]
    with bench.context:
        lm = family.build_lm(config, seed32(bench.seed))
        eng = serving.DecodeEndpoint(
            NAME, lm, max_seq_len=cell["max_seq_len"],
            max_batch_size=cell["max_batch_size"],
            num_pages=cell["num_pages"])
        bench.phase_done("build")
        server = serving.InferenceServer()
        server.register_generator(eng)         # warms every executable
        server.start()
    requests = make_requests(cell, vocab, seed32(bench.seed, 1))
    warm_compiles = eng.stats.snapshot()["counters"]["compiles"]
    bench.say({"executables": warm_compiles,
               "prefill_buckets": list(eng.prefill_buckets),
               "decode_buckets": list(eng.decode_buckets),
               "prompt_len": _dist([len(p) for p, _ in requests]),
               "output_len": _dist([b for _, b in requests])})

    clients = _Clients(server, requests, cell["clients"],
                       cell["request_timeout_s"], cell.get("generate"))
    clients.start()
    # warm-up traffic: until every caller's slot has turned over once, or
    # the cell's limit, whichever comes first
    limit = time.perf_counter() + cell["warmup_seconds"]
    while time.perf_counter() < limit and \
            len(clients.turned_over) < cell["clients"]:
        time.sleep(0.01)
    bench.setup_done()

    c0 = bench.clock.read()["compiles"]
    steps0 = eng.stats.snapshot()["counters"]["steps"]
    t0 = time.perf_counter()
    time.sleep(bench.seconds)
    t1 = time.perf_counter()
    steps = eng.stats.snapshot()["counters"]["steps"] - steps0
    compiles = bench.clock.read()["compiles"] - c0
    if bench.trace:
        with bench.traced_window():
            time.sleep(cell["trace_seconds"])
    clients.stop_and_drain()
    server.stop(drain=True)
    compiles += eng.stats.snapshot()["counters"]["compiles"] - warm_compiles
    # read before the check's reference runs on the chip: a process's peak
    # never falls again. (The pool stays: the kept streams hold the scheduler;
    # a block of the reference's logits is 0.33 GB beside it.)
    bench.memory_peak_bytes()

    done = clients.done
    failed = [r for r in done if r.error is not None
              or len(r.tokens) != r.budget]
    good = [r for r in done if r.error is None]
    in_window = [r for r in done if t0 <= r.submitted < t1]
    ttft = [1e3 * (r.stamps[0] - r.submitted) for r in in_window if r.stamps]
    gaps, tokens, later_tokens = [], 0, 0
    for r in done:
        for j, t in enumerate(r.stamps):
            if t0 <= t < t1:
                tokens += 1
                if j:
                    later_tokens += 1
                    gaps.append(1e3 * (t - r.stamps[j - 1]))
    if not good:
        ok, seen = False, {}
    elif hasattr(family, "check_requests"):
        ok, seen = family.check_requests(bench, lm, good, vocab)
    else:
        ok, seen = _check_requests(bench, lm, family, good, vocab)
    # a family's check names what it compared under ``compared``
    compared = seen.pop("compared", None) or {"worst_logit_deficit": {
        "value": seen.get("worst_logit_deficit"),
        "limit": seen.get("logit_tolerance")}}
    compared["compiles_after_warmup"] = {"value": compiles, "limit": 0}
    compared["failed_requests"] = {"value": len(failed), "limit": 0}
    checks = {"answers": ok, "no_compile_after_warmup": compiles == 0,
              "none_failed": not failed}
    bench.say({"check": checks, **seen, "compiles_after_warmup": compiles,
               "errors": sorted({type(r.error).__name__ for r in done
                                 if r.error is not None})})
    bench.say({"window_s": t1 - t0, "requests_submitted": len(in_window),
               "requests_finished_in_run": len(done), "tokens": tokens,
               "decode_steps": steps, "ttft_ms": _dist(ttft),
               "tpot_ms": _dist(gaps)})
    return {"correct": all(checks.values()),
            "attempted": len(done), "failed": len(failed),
            "end_to_end": {"decode_tokens_per_s": tokens / (t1 - t0)},
            "compared": compared,
            # for the per-layer readers
            "ttft_p95_ms": percentile(ttft, 95),
            "tpot_p95_ms": percentile(gaps, 95),
            "later_tokens": later_tokens, "decode_steps": steps,
            "max_batch_size": cell["max_batch_size"]}


def _dist(values):
    return {"n": len(values), "min": min(values, default=None),
            "p50": median(values), "p95": percentile(values, 95),
            "max": max(values, default=None)}
