"""Decode-path observability: per-endpoint counters for the generative loop.

Same discipline as serving/stats.py — shared-registry families labeled by
endpoint, children pre-bound at construction so the per-step/per-token cost
is one counter bump, and fine-resolution local LatencyHistograms behind the
``snapshot()`` dict for exact percentiles (the registry histograms serve the
export surface). The load-bearing numbers are the gate metrics: decode
tokens/steps (tok/s/chip once divided by wall clock and chip count) and the
inter-token latency distribution (the per-tenant SLO unit).
"""
from __future__ import annotations

import threading
from typing import Dict

from ... import telemetry as _telemetry
from ..stats import LatencyHistogram

__all__ = ["DecodeStats"]

_TOKENS = _telemetry.counter(
    "mxtpu_decode_tokens_total",
    "Tokens emitted to client streams (prefill first-tokens included).",
    labelnames=("endpoint",))
_STEPS = _telemetry.counter(
    "mxtpu_decode_steps_total",
    "Batched decode steps executed (each advances every running sequence "
    "by one token).",
    labelnames=("endpoint",))
_ROWS = _telemetry.counter(
    "mxtpu_decode_rows_total",
    "Rows of live sequences forwarded by decode steps: one a sequence a step "
    "for a causal model, two blocks of block_length a sequence for "
    "generation by diffusion over blocks (the block in hand and the one "
    "behind it).",
    labelnames=("endpoint",))
_COMMITS = _telemetry.counter(
    "mxtpu_decode_blocks_committed_total",
    "Sequence-steps whose K/V were written to the pool: every row of a "
    "causal step; for block diffusion the forward that commits a finished "
    "block, which is the next block's first denoising step (the other "
    "denoising steps write nothing).",
    labelnames=("endpoint",))
_PLACED = _telemetry.counter(
    "mxtpu_decode_tokens_placed_total",
    "Tokens fixed in their sequences by decode steps (0 to block_length a "
    "sequence a step); they reach the stream in sequence order, so this "
    "runs ahead of mxtpu_decode_tokens_total by what is still held back.",
    labelnames=("endpoint",))
_EXPERT_LOAD = _telemetry.gauge(
    "mxtpu_moe_expert_load_max_over_mean",
    "Rows routed to the busiest expert over the mean rows an expert, at "
    "the last decode step (mean over layers): the straggler a grouped "
    "expert product waits for.",
    labelnames=("endpoint",))
_SEQS = _telemetry.counter(
    "mxtpu_decode_seqs_total",
    "Sequence lifecycle events: submitted / admitted / finished / "
    "cancelled / failed / requeued (failover) / paused / resumed "
    "(stream backpressure).",
    labelnames=("endpoint", "event"))
_OCCUPANCY = _telemetry.gauge(
    "mxtpu_decode_batch_occupancy",
    "Running sequences / padded batch bucket at the last decode step "
    "(0..1); persistently low means the bucket ladder is too coarse for "
    "the offered concurrency.",
    labelnames=("endpoint",))
_QUEUE_DEPTH = _telemetry.gauge(
    "mxtpu_decode_queue_depth",
    "Sequences admitted-but-waiting for a batch slot or KV pages.",
    labelnames=("endpoint",))
_INTERTOKEN = _telemetry.histogram(
    "mxtpu_decode_intertoken_us",
    "Gap between consecutive tokens of one sequence as emitted by the "
    "scheduler (microseconds) — the unit per-tenant decode SLOs are "
    "expressed in.",
    labelnames=("endpoint", "tenant"))
_PREFILL = _telemetry.histogram(
    "mxtpu_decode_prefill_us",
    "Prefill executable latency per admitted sequence (microseconds).",
    labelnames=("endpoint",))
_STEP_LAT = _telemetry.histogram(
    "mxtpu_decode_step_us",
    "Batched decode-step executable latency (microseconds).",
    labelnames=("endpoint",))
_QUEUE_WAIT = _telemetry.histogram(
    "mxtpu_decode_queue_wait_us",
    "Time a sequence waited from submit() until the scheduler admitted it "
    "into the batch (microseconds); a failover requeue is not counted again.",
    labelnames=("endpoint",))
_TTFT = _telemetry.histogram(
    "mxtpu_decode_ttft_us",
    "Time from submit() until the scheduler emitted the sequence's first "
    "token to its stream: queue wait + the prefills ahead + its own "
    "prefill (microseconds).",
    labelnames=("endpoint",))
_BACKPRESSURE = _telemetry.counter(
    "mxtpu_decode_stream_backpressure_total",
    "Sequences paused because their client stream buffer filled; the "
    "sequence keeps its KV pages and resumes when the consumer drains.",
    labelnames=("endpoint",))
_FAILOVERS = _telemetry.counter(
    "mxtpu_decode_failovers_total",
    "Decode-worker failovers by reason (worker_dead = the loop thread "
    "died, e.g. an injected decode_stall); running sequences are requeued "
    "with pages and emitted tokens intact.",
    labelnames=("endpoint", "reason"))

_SEQ_EVENTS = ("submitted", "admitted", "finished", "cancelled", "failed",
               "requeued", "paused", "resumed")


class DecodeStats:
    """Counters + histograms for one decode endpoint."""

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self.counters: Dict[str, int] = {
            "tokens": 0, "steps": 0, "compiles": 0,
            # a step is one forward of every running sequence; what it did
            # is counted apart, so that nothing divides tokens by steps
            "forwards": 0, "commits": 0, "rows": 0, "tokens_placed": 0,
            "blocks_committed": 0,
            # of them, by a forward that was the first denoising step of the
            # block behind it (counted where that step's result is absorbed).
            # Every commit a block scheduler sends is one, so the two are
            # equal but for a forward whose result was never absorbed (a
            # sequence cancelled under it); 0 for a causal model
            "commits_merged": 0,
            # cached positions the steps' lanes attended to, of those their
            # lanes could hold (lanes x max_seq_len)
            # ctx_window_live: of them, those a layer that keeps a window
            # has to read (0 of a model without such layers)
            "ctx_live": 0, "ctx_capacity": 0, "ctx_bytes": 0,
            "ctx_window_live": 0,
            "moe.expert_load_max": 0.0, "moe.expert_load_mean": 0.0,
            # how often a pass's order engages: prefills launched while a
            # step was in flight, of all; and the time steps' fetches
            # blocked (the chip set the pace for that long, the host for
            # the rest)
            "prefills": 0, "prefills_overlapped": 0, "step_fetch_wait_us": 0,
            **{f"seq_{ev}": 0 for ev in _SEQ_EVENTS},
        }
        self.prefill = LatencyHistogram()
        self.step = LatencyHistogram()
        self.intertoken = LatencyHistogram()
        self.queue_wait = LatencyHistogram()
        self.ttft = LatencyHistogram()
        self._m_tokens = _TOKENS.labels(name)
        self._m_steps = _STEPS.labels(name)
        self._m_rows = _ROWS.labels(name)
        self._m_commits = _COMMITS.labels(name)
        self._m_placed = _PLACED.labels(name)
        self._m_expert_load = _EXPERT_LOAD.labels(name)
        self._m_seqs = {ev: _SEQS.labels(name, ev) for ev in _SEQ_EVENTS}
        self._m_occupancy = _OCCUPANCY.labels(name)
        self._m_queue_depth = _QUEUE_DEPTH.labels(name)
        self._m_prefill = _PREFILL.labels(name)
        self._m_step = _STEP_LAT.labels(name)
        self._m_queue_wait = _QUEUE_WAIT.labels(name)
        self._m_ttft = _TTFT.labels(name)
        self._m_backpressure = _BACKPRESSURE.labels(name)
        self._m_intertoken: Dict[str, object] = {}

    def seq_event(self, event: str, delta: int = 1):
        with self._lock:
            self.counters[f"seq_{event}"] += delta
        self._m_seqs[event].inc(delta)

    def tokens(self, n: int = 1):
        with self._lock:
            self.counters["tokens"] += n
        self._m_tokens.inc(n)

    def record_step(self, dur_us: float, seqs: int, bucket: int, *,
                    rows: int = None, commits: int = None, expert_load=(),
                    ctx=(0, 0), ctx_bytes: int = 0, ctx_window: int = 0,
                    fetch_wait_us: int = 0):
        """One step executable run over ``seqs`` sequences padded to
        ``bucket``: ``rows`` forwarded (default one a sequence), ``commits``
        of them writing their K/V (default all), and where the model routes
        experts ``expert_load`` = (rows routed to the busiest expert, to an
        expert on average) of the step, summed into ``moe.expert_load_max``
        / ``_mean`` so that a window's ratio is a difference of sums.
        ``ctx`` = (cached positions the sequences attended to, positions
        their lanes can hold): what the step's attention read of what a
        gather of all lanes would have, and ``ctx_bytes`` the bytes of the
        pool those positions are (positions x the pool's row over all layers
        and arrays: a latent pool's row once, a K and a V row otherwise;
        summed over the pool's cache groups, each at the positions its
        layers read, ``ctx_window`` of them in a group that keeps a window):
        what the step's attention had to read. ``dur_us`` runs from the step's
        launch until its result was in hand, ``fetch_wait_us`` is the part of
        it the fetch blocked."""
        rows = seqs if rows is None else rows
        commits = seqs if commits is None else commits
        with self._lock:
            self.counters["steps"] += 1
            self.counters["forwards"] += 1
            self.counters["commits"] += commits > 0
            self.counters["rows"] += rows
            self.counters["blocks_committed"] += commits
            self.counters["ctx_live"] += ctx[0]
            self.counters["ctx_capacity"] += ctx[1]
            self.counters["ctx_bytes"] += ctx_bytes
            self.counters["ctx_window_live"] += ctx_window
            self.counters["step_fetch_wait_us"] += fetch_wait_us
            if expert_load:
                self.counters["moe.expert_load_max"] += expert_load[0]
                self.counters["moe.expert_load_mean"] += expert_load[1]
            self.step.record(dur_us)
        self._m_steps.inc()
        self._m_rows.inc(rows)
        self._m_commits.inc(commits)
        self._m_step.observe(dur_us)
        self._m_occupancy.set(seqs / bucket if bucket else 0.0)
        if expert_load and expert_load[1]:
            self._m_expert_load.set(expert_load[0] / expert_load[1])

    def placed(self, n: int):
        """``n`` tokens fixed in their sequences by a step."""
        with self._lock:
            self.counters["tokens_placed"] += n
        self._m_placed.inc(n)

    def commit_merged(self):
        """A block's commit rode in the forward whose result, now absorbed,
        is the first denoising step of the block behind it."""
        with self._lock:
            self.counters["commits_merged"] += 1

    def record_prefill(self, dur_us: float, overlapped: bool = False):
        """One prefill, ``dur_us`` from its launch until its first token was
        in hand; ``overlapped`` where it was launched while a step was in
        flight."""
        with self._lock:
            self.counters["prefills"] += 1
            self.counters["prefills_overlapped"] += bool(overlapped)
            self.prefill.record(dur_us)
        self._m_prefill.observe(dur_us)

    def record_intertoken(self, tenant: str, dur_us: float):
        with self._lock:
            self.intertoken.record(dur_us)
            child = self._m_intertoken.get(tenant)
            if child is None:
                child = self._m_intertoken.setdefault(
                    tenant, _INTERTOKEN.labels(self.name, tenant))
        child.observe(dur_us)

    def record_queue_wait(self, dur_us: float):
        with self._lock:
            self.queue_wait.record(dur_us)
        self._m_queue_wait.observe(dur_us)

    def record_ttft(self, dur_us: float):
        with self._lock:
            self.ttft.record(dur_us)
        self._m_ttft.observe(dur_us)

    def record_compile(self):
        with self._lock:
            self.counters["compiles"] += 1

    def backpressure(self):
        self._m_backpressure.inc()

    def failover(self, reason: str):
        _FAILOVERS.labels(self.name, reason).inc()

    def set_queue_depth(self, n: int):
        self._m_queue_depth.set(n)

    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "counters": dict(self.counters),
                "ctx_live": self.counters["ctx_live"],
                "ctx_window_live": self.counters["ctx_window_live"],
                "ctx_live_share": self.counters["ctx_live"]
                / max(1, self.counters["ctx_capacity"]),
                "prefill": self.prefill.snapshot(),
                "step": self.step.snapshot(),
                "intertoken": self.intertoken.snapshot(),
                "queue_wait": self.queue_wait.snapshot(),
                "ttft": self.ttft.snapshot(),
            }
