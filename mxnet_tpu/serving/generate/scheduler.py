"""DecodeScheduler: token-granularity continuous batching for one endpoint.

Unlike the request-batched InferenceServer — where a batch forms once and
runs to completion — the decode batch is re-formed *every step*: a finished
sequence leaves at the step boundary it emits EOS (its pages free
immediately), and a waiting sequence joins the moment a slot and pages are
available, without waiting for the rest of the batch to finish. Admission is
EDF over waiting sequences, slack priced with the live per-token step cost
(``StepCostEWMA`` over decode buckets), against per-tenant SLOs expressed as
inter-token latency.

Correctness invariants (the chaos scenario asserts all three):

- **Atomic emission**: a token is appended to the client stream and the
  sequence's position advanced under one lock, *after* the device step
  completes. A worker that dies mid-step has emitted nothing for that step.
- **Whole-budget reservation**: ``ceil((prompt+max_new)/page_size)`` pages
  are reserved at admission (in every cache group of the pool, all or
  nothing: a group that keeps a window reserves its ring; ``free`` returns
  every group's), so KV exhaustion can only happen *before* a
  sequence starts — it stays queued (``KVPoolExhausted`` is absorbed) and
  there is never a half-generated sequence to unwind or re-prefill (which
  would not be bitwise-safe across the prefill/decode paths).
- **Failover requeues, never replays**: a monitor thread polls the worker's
  liveness; on death every RUNNING sequence goes back to the waiting queue
  with its pages, position and emitted tokens intact (``prefilled=True``
  skips re-prefill), the epoch fences the zombie out, and a fresh worker
  continues each sequence at exactly the next token — no duplicates, no
  drops, bitwise-identical output.

Backpressure is lossless: a full client stream pauses the sequence (state
PAUSED, pages kept, not stepped); the stream's resume callback re-runs it.

A pass launches before it waits. The worker launches the step of the lanes
that are running, and while the chip runs it admits waiting sequences and
launches their prefills behind it; only then does it fetch the step's
tokens, emit them, and fetch and emit the prefills' first tokens
(``_iteration``). The chip runs the calls in the order of their launches,
each on the pools the one before it returned, so the tokens are those of a
pass that waited after every launch; a token still reaches its stream in
the pass that fetched it, and a sequence admitted in a pass joins the next
pass's step.

A step need not yield one token a sequence. An endpoint whose model is
generated by diffusion over blocks (``block_length`` L > 1) steps 2L rows a
sequence, its block at ``pos`` and the block behind it, still all mask
tokens (a *row*, ``_row_locked``), and a pass places 0 to L tokens in it: a
denoising step fixes the ``L / denoising_steps`` still-masked positions of
highest confidence, and when none is left the block's tokens go to the
stream, in sequence order, each with the step it was placed at and the
confidence it was placed with. The next pass's forward *commits* the whole
block's K/V and is at once the first denoising step of the block behind it,
which saw the whole block in the same forward: absorbing its result moves
``pos`` on, starts that block and places its first tokens. So a block costs
``denoising_steps`` forwards and no forward of its own to commit it; a
sequence's last block is finished when it is whole and is never committed
(nobody reads its K/V). Lanes of one batch are at different steps of their
blocks. The prompt's whole blocks are prefilled; its ragged tail fills the
first positions of the first block, and positions past ``max_new_tokens`` in
the last block stay masked. All of a block's state lives on the sequence,
"whole and not yet committed" too, so a failover requeue or a paused stream
resumes where it stood and commits in its next forward.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

from ... import config as _config
from ... import telemetry as _telemetry
from ...base import MXNetError
from ...resilience import faults as _faults
from ...resilience.faults import FaultInjected
from ...telemetry import flight as _flight
from .. import bucketing
from .. import tailguard as _tailguard
from ..errors import DeadlineExceeded, KVPoolExhausted, ServerClosedError
from .streams import TokenStream

__all__ = ["DecodeScheduler"]

_RUNNING, _DRAINING, _STOPPED = "running", "draining", "stopped"

# sequence states
_S_WAITING, _S_RUNNING, _S_PAUSED = "waiting", "running", "paused"
_S_DONE, _S_FAILED, _S_CANCELLED = "done", "failed", "cancelled"

# _Seq.placed, beside the step numbers
_GIVEN, _PAST = -1, -2

# what a pass of the decode loop tells the loop to do next
_EXIT, _AGAIN, _REST = "exit", "again", "rest"


def _now_us() -> int:
    return time.perf_counter_ns() // 1000


class _Tenant:
    __slots__ = ("name", "slo_us")

    def __init__(self, name: str, slo_us: float):
        self.name = name
        self.slo_us = float(slo_us)


class _Seq:
    __slots__ = ("sid", "tenant", "prompt", "max_new", "eos_id", "stream",
                 "state", "emitted", "pos", "prefilled", "enqueue_us",
                 "admitted", "last_token_us", "deadline", "trace_id",
                 "per_step", "block", "placed", "conf", "step")

    def __init__(self, sid: int, tenant: _Tenant, prompt: Sequence[int],
                 max_new: int, eos_id: Optional[int], stream: TokenStream,
                 deadline=None, per_step: int = 1):
        self.sid = sid
        self.tenant = tenant
        self.prompt = list(prompt)
        self.max_new = max_new
        self.eos_id = eos_id
        self.stream = stream
        self.state = _S_WAITING
        self.emitted: List[int] = []
        self.pos = len(self.prompt)      # tokens materialised in the KV cache
        self.prefilled = False
        self.enqueue_us = _now_us()
        self.admitted = False            # a failover requeue admits again
        self.last_token_us = 0
        self.deadline = deadline         # propagated tailguard.Deadline
        # the submitter's trace: its own span, this sequence's wait and its
        # decode.prefill share one id across the queue hop
        self.trace_id = (_telemetry.current_trace_id()
                         or _telemetry.new_trace_id())
        # generation by diffusion over blocks: tokens placed a denoising
        # step, the current block's ids, and for each of its positions the
        # step it was placed at (_GIVEN: the prompt's tail; None: masked, to
        # be placed; _PAST: beyond the budget, masked for good)
        self.per_step = per_step
        self.block: List[int] = []
        self.placed: list = []
        self.conf: list = []             # the confidence each was placed with
        self.step = 0


class DecodeScheduler:
    """Continuous-batching loop over one :class:`DecodeEndpoint`.

    One worker thread owns all device work (prefill + decode steps); a
    monitor thread supervises it and drives failover. Clients interact only
    through :meth:`submit` and the returned :class:`TokenStream`.
    """

    def __init__(self, engine, *, default_slo_ms: Optional[float] = None,
                 stream_buffer: Optional[int] = None,
                 poll_s: Optional[float] = None):
        self.engine = engine
        self._stats = engine.stats
        if default_slo_ms is None:
            default_slo_ms = float(_config.get("MXNET_DECODE_SLO_MS"))
        self._default_slo_us = default_slo_ms * 1000.0
        self._stream_buffer = int(
            stream_buffer if stream_buffer is not None
            else _config.get("MXNET_DECODE_STREAM_BUFFER"))
        self._poll_s = float(poll_s if poll_s is not None
                             else _config.get("MXNET_SUPERVISOR_POLL_S"))
        self._cond = threading.Condition(threading.Lock())
        self._state = _STOPPED
        self._epoch = 0
        self._thread: Optional[threading.Thread] = None
        self._monitor: Optional[threading.Thread] = None
        self._monitor_stop = threading.Event()
        self._waiting: deque = deque()
        self._active: List[_Seq] = []       # RUNNING + PAUSED, batch order
        self._by_sid: Dict[int, _Seq] = {}
        self._sids = itertools.count(1)
        self._tenants: Dict[str, _Tenant] = {
            "default": _Tenant("default", self._default_slo_us)}
        self.reports: list = []             # failover reports, newest last

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def add_tenant(self, name: str, slo_ms: Optional[float] = None
                   ) -> "DecodeScheduler":
        """Register a tenant with its inter-token SLO (ms per token)."""
        slo_us = (float(slo_ms) * 1000.0 if slo_ms is not None
                  else self._default_slo_us)
        with self._cond:
            self._tenants[name] = _Tenant(name, slo_us)
        return self

    def start(self) -> "DecodeScheduler":
        with self._cond:
            if self._state == _RUNNING:
                return self
            self._state = _RUNNING
            self._spawn_worker_locked()
        self._monitor_stop.clear()
        self._monitor = threading.Thread(
            target=self._monitor_loop, name=f"mxtpu-decode-mon-{self.engine.name}",
            daemon=True)
        self._monitor.start()
        return self

    def _spawn_worker_locked(self):    # mxlint: disable=CONC200
        self._epoch += 1
        self._thread = threading.Thread(
            target=self._loop, args=(self._epoch,),
            name=f"mxtpu-decode-{self.engine.name}-gen{self._epoch}",
            daemon=True)
        self._thread.start()

    def stop(self, drain: bool = True, timeout: Optional[float] = None):
        """Stop the loop. ``drain=True`` (graceful) finishes every in-flight
        AND waiting sequence first, refusing new submits; past ``timeout``
        seconds the remainder fail with ServerClosedError."""
        if timeout is None:
            timeout = float(_config.get("MXNET_SERVING_DRAIN_TIMEOUT_S"))
        with self._cond:
            if self._state == _STOPPED and self._thread is None:
                return
            self._state = _DRAINING if drain else _STOPPED
            t = self._thread
            self._cond.notify_all()
        if t is not None:
            t.join(timeout=timeout if drain else 2.0)
        self._monitor_stop.set()
        m, self._monitor = self._monitor, None
        with self._cond:
            self._state = _STOPPED
            self._cond.notify_all()
            leftovers = list(self._active) + list(self._waiting)
            self._active.clear()
            self._waiting.clear()
            for seq in leftovers:
                self._retire_locked(
                    seq, _S_FAILED, "failed",
                    error=ServerClosedError(
                        f"decode scheduler for {self.engine.name!r} stopped "
                        f"before sequence {seq.sid} finished"))
            self._thread = None
        if t is not None and t.is_alive():
            t.join(timeout=2.0)
        if m is not None:
            m.join(timeout=self._poll_s * 4 + 1.0)

    def __enter__(self) -> "DecodeScheduler":
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # ------------------------------------------------------------------
    # client surface
    # ------------------------------------------------------------------
    def submit(self, prompt: Sequence[int],
               max_new_tokens: Optional[int] = None,
               tenant: str = "default", eos_id: Optional[int] = None,
               on_token=None, deadline=None,
               denoising_steps: Optional[int] = None) -> TokenStream:
        """Queue one generation; returns its :class:`TokenStream`.

        The prompt plus generation budget must fit the endpoint's
        ``max_seq_len`` — the whole KV budget is reserved at admission so a
        running sequence can never hit pool exhaustion mid-generation.

        ``deadline`` (a propagated :class:`~..tailguard.Deadline`) bounds
        the whole generation: an expired budget refuses admission, and the
        decode loop retires the sequence mid-generation the moment the
        budget runs out (site ``decode_token``). Under brownout (level >= 1)
        ``max_new_tokens`` is clamped to MXNET_BROWNOUT_MAX_NEW_TOKENS —
        generations shorten before anyone is refused.

        ``denoising_steps`` (endpoints generated by diffusion over blocks):
        forwards spent on a block, each placing ``block_length /
        denoising_steps`` tokens; default ``block_length``, one a step.
        """
        if deadline is not None:
            deadline.check("ingress")
        if max_new_tokens is None:
            max_new_tokens = int(_config.get("MXNET_DECODE_MAX_TOKENS"))
        max_new_tokens = _tailguard.BROWNOUT.clamp_max_new_tokens(
            max_new_tokens)
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise MXNetError("prompt must contain at least one token")
        if max_new_tokens < 1:
            raise MXNetError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        L = self.engine.block_length
        if denoising_steps is None:
            denoising_steps = L
        if denoising_steps < 1 or L % denoising_steps:
            raise MXNetError(
                f"denoising_steps must divide the endpoint's block_length "
                f"{L}, got {denoising_steps}")
        total = len(prompt) + max_new_tokens
        if total > self.engine.max_seq_len:
            raise MXNetError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) = {total} exceeds max_seq_len "
                f"{self.engine.max_seq_len}")
        with self._cond:
            if self._state != _RUNNING:
                raise ServerClosedError(
                    f"decode scheduler for {self.engine.name!r} is "
                    f"{self._state}; not accepting new sequences")
            ten = self._tenants.get(tenant)
            if ten is None:
                raise MXNetError(f"unknown tenant {tenant!r}; registered: "
                                 f"{sorted(self._tenants)}")
            sid = next(self._sids)
            stream = TokenStream(sid, self._stream_buffer,
                                 on_token=on_token, resume_cb=self._resume)
            seq = _Seq(sid, ten, prompt, int(max_new_tokens), eos_id, stream,
                       deadline=deadline, per_step=L // denoising_steps)
            self._waiting.append(seq)
            self._by_sid[sid] = seq
            self._stats.seq_event("submitted")
            self._stats.set_queue_depth(len(self._waiting))
            self._cond.notify_all()
        return stream

    def _resume(self, sid: int):
        """Stream resume callback (consumer thread, stream lock NOT held)."""
        with self._cond:
            seq = self._by_sid.get(sid)
            if seq is not None and seq.state == _S_PAUSED:
                seq.state = _S_RUNNING
                self._stats.seq_event("resumed")
                self._cond.notify_all()

    # ------------------------------------------------------------------
    # the decode loop (worker thread)
    # ------------------------------------------------------------------
    def _loop(self, epoch: int):
        verdict = _REST
        while verdict != _EXIT:
            if verdict == _REST:
                # the last pass left nothing to run: the fence checks and
                # the wait happen here, under no span
                with self._cond:
                    if self._epoch != epoch:
                        return          # fenced-out zombie generation
                    if self._state == _STOPPED:
                        return
                    if not self._runnable_locked():
                        if self._active:
                            self._cond.wait(0.005)      # all paused
                        elif self._state == _DRAINING:
                            return
                        else:
                            self._cond.wait(0.05)
                        continue
            verdict = self._iteration(epoch)

    def _runnable_locked(self) -> bool:    # mxlint: disable=CONC200
        return bool(self._waiting) or any(
            s.state == _S_RUNNING for s in self._active)

    def _iteration(self, epoch: int) -> str:
        """One working pass under a ``decode.iteration`` span, in the order
        that keeps the chip and this thread busy at once: build the batch of
        the lanes that are running now and *launch* its step; in the step's
        shadow, while the chip runs it, admit into the free slots and launch
        the prefill of each admitted sequence (:meth:`_admit_and_launch`:
        they queue behind the step on the chip); finish the step and emit
        its tokens; finish the prefills, which ran while the step's tokens
        were emitted, and emit their first tokens. A sequence admitted in a
        pass joins the next pass's step. With nothing running (start, drain,
        all paused) the pass admits and prefills with no step in flight.

        Tells the loop what comes next: ``_EXIT`` once this generation is
        fenced out or the scheduler stopped (every lock take checks the
        fence), ``_AGAIN`` while its last look under the lock saw more to
        run (the next pass then starts with ``decode.build`` taking the
        lock, so that waiting for it is inside a span), else ``_REST``.
        The pass's span takes its thread's CPU time too (the one span of
        a pass that does: two system calls): wall time less that, less the
        fetches that waited for the chip, is the loop standing without the
        processor for something on the host."""
        with _telemetry.span("decode.iteration", cpu=True, admits=0,
                             rows=0) as it:
            with _telemetry.span("decode.build") as sp:
                with self._cond:
                    if self._epoch != epoch or self._state == _STOPPED:
                        return _EXIT
                    # the per-token deadline hop: a sequence whose
                    # end-to-end budget ran out mid-generation is retired
                    # BEFORE it costs another device step
                    for s in list(self._active):
                        if s.state == _S_RUNNING and s.deadline is not None \
                                and s.deadline.expired():
                            _tailguard.deadline_expired("decode_token")
                            self._fail_seq_locked(s, DeadlineExceeded(
                                f"sequence {s.sid} overran its deadline "
                                f"after {len(s.emitted)} of {s.max_new} "
                                "tokens"))
                    seqs = [s for s in self._active if s.state == _S_RUNNING]
                    # what each sends to the step, beside it (no per-lane
                    # unpacking on this path: 64 lanes a pass)
                    rows = [self._row_locked(s) for s in seqs]
                sp.attrs["rows"] = it.attrs["rows"] = len(seqs)
            prefills, toks = [], None
            if not seqs:
                prefills = self._admit_and_launch(epoch, it)
            else:
                try:
                    _faults.check("decode")
                    with _telemetry.span(
                            "decode.step",
                            rows=len(seqs) * self.engine.step_rows,
                            bucket=bucketing.bucket_for(
                                len(seqs), self.engine.decode_buckets),
                            placed=sum(map(self._places, seqs, rows))) as sp:
                        step = self.engine.launch_step(rows)
                        prefills = self._admit_and_launch(epoch, it)
                        toks = self.engine.finish_step(step)
                        sp.attrs.update(self.engine.last_step)
                except FaultInjected as e:
                    _telemetry.event("decode_fault_absorbed", kind=e.kind,
                                     endpoint=self.engine.name)
                    return _REST            # transient: re-form and retry
                except Exception as e:
                    # an error that surfaces at the step's fetch is the
                    # chip's: the prefills launched in its shadow read and
                    # donated the pools that step returned, so their
                    # sequences fail with the step's
                    with self._cond:
                        for s in seqs + [p[0] for p in prefills or ()]:
                            self._fail_seq_locked(s, e)
                    return _REST
            if prefills is None:
                return _EXIT
            if toks is not None:
                # one decode step = one unit of real work funding the decode
                # tier's retry budget (failover requeues spend from it)
                _tailguard.retry_deposit("decode")
                with _telemetry.span("decode.emit") as sp:
                    with self._cond:
                        if self._epoch != epoch:
                            return _EXIT    # died-and-replaced mid-step:
                                            # the new generation already
                                            # owns these sequences;
                                            # emitting now would dup
                        live = [(s, row[3], out) for s, row, out
                                in zip(seqs, rows, toks)
                                # the others were retired concurrently
                                if s.state in (_S_RUNNING, _S_PAUSED)]
                        before = self._stats.counters["tokens"]
                        self._stats.placed(sum(
                            self._absorb_locked(s, commit, out)
                            for s, commit, out in live))
                        self._stats.set_queue_depth(len(self._waiting))
                        sp.attrs["tokens"] = \
                            self._stats.counters["tokens"] - before
            for launched in prefills:
                if not self._finish_prefill(*launched, epoch):
                    return _EXIT
            with self._cond:
                if not seqs and not it.attrs["admits"]:
                    self._cond.wait(0.005)      # pool-blocked
                    return _REST
                return _AGAIN if self._runnable_locked() else _REST

    # -- what a sequence sends to a step, and takes from it -----------------
    def _row_locked(self, s: "_Seq"):    # mxlint: disable=CONC200
        """``(ids, first position, page table, commit)`` for the engine: the
        last token at its position, always committing, or the current block
        with the block behind it, and whether the current one is whole and
        this forward writes its K/V. The block behind is mask tokens (all
        that is known lies in the current block), and padding that nothing
        reads where the sequence ends with this one."""
        table = self.engine.pool.table(s.sid)
        L = self.engine.block_length
        if L == 1:
            return s.emitted[-1], s.pos, table, True
        return s.block + [self.engine.mask_token_id] * L, s.pos, table, \
            None not in s.placed

    def _places(self, s: "_Seq", row) -> int:
        """Tokens the step about to run fixes in ``s``, which sends it
        ``row``: of a forward that commits, in the block behind."""
        L = self.engine.block_length
        if L == 1:
            return 1
        if row[3]:
            return min(s.per_step, len(s.prompt) + s.max_new - s.pos - L)
        return min(s.per_step, s.placed.count(None))

    def _start_block_locked(self, s: "_Seq"):    # mxlint: disable=CONC200
        """Lay out the block at ``s.pos``: what is known of it (the prompt's
        ragged tail, in the first block only: what was emitted lies in the
        blocks before), then mask tokens."""
        L = self.engine.block_length
        end = len(s.prompt) + s.max_new
        known = s.prompt[s.pos:s.pos + L]
        s.block = known + [self.engine.mask_token_id] * (L - len(known))
        s.placed = [_GIVEN] * len(known) + [
            None if s.pos + i < end else _PAST for i in range(len(known), L)]
        s.conf = [None] * L
        s.step = 0

    def _absorb_locked(self, s: "_Seq", commit: bool,    # mxlint: disable=CONC200
                       out) -> int:
        """Take one step's result into ``s``; returns the tokens it fixed.
        One token a step: ``out`` is the next token. Blocks: ``out`` is
        (ids, confidences) of a block's rows: a forward that committed moves
        on to the block behind, whose rows they then are (its first
        denoising step), else the current block's. A denoising step places
        its ``per_step`` most confident masked positions (ties to the lower
        one), and once the block is whole its tokens are emitted."""
        if self.engine.block_length == 1:
            s.pos += 1
            self._emit_locked(s, out)
            return 1
        if commit:
            s.pos += self.engine.block_length
            self._start_block_locked(s)
            self._stats.commit_merged()
        ids, conf = out
        masked = [i for i, at in enumerate(s.placed) if at is None]
        masked.sort(key=lambda i: (-conf[i], i))
        chosen = masked[:s.per_step]
        for i in chosen:
            s.block[i], s.placed[i] = int(ids[i]), s.step
            s.conf[i] = float(conf[i])
        s.step += 1
        if None not in s.placed:
            for tok, at, sure in zip(s.block, s.placed, s.conf):
                if at >= 0 and s.state in (_S_RUNNING, _S_PAUSED):
                    self._emit_locked(s, tok, at, sure)
        return len(chosen)

    def _admit_and_launch(self, epoch: int, it) -> Optional[list]:
        """Admit from the waiting queue into the free slots and launch the
        prefill of each admitted sequence, back to back and waiting for
        none: behind a step in flight they queue on the chip and run while
        this thread goes on. ``it`` is the pass's span, which these hang
        under whatever is open around the call. Returns what
        :meth:`_finish_prefill` takes, per prefill launched; None once this
        generation is fenced out."""
        with _telemetry.span("decode.admit", parent=it) as sp:
            with self._cond:
                if self._epoch != epoch:
                    return None
                sp.attrs["waiting"] = len(self._waiting)
                admits = self._admit_locked()
            sp.attrs["admitted"] = it.attrs["admits"] = len(admits)
        L = self.engine.block_length
        launched = []
        for seq in admits:
            if seq.prefilled:
                continue    # requeued by failover: pages intact
            # generation by blocks prefills the prompt's whole blocks: the
            # ragged tail opens the first block
            n = len(seq.prompt) // L * L
            with _telemetry.span(
                    "decode.prefill", trace_id=seq.trace_id, parent=it,
                    sid=seq.sid, prompt_len=len(seq.prompt),
                    # the rung it runs at and the rows of it that are the
                    # prompt's: the rest is padding
                    tokens=n, bucket=bucketing.bucket_for(
                        n, self.engine.prefill_buckets),
                    queue_wait_us=_now_us() - seq.enqueue_us) as sp:
                try:
                    call = self.engine.launch_prefill(
                        seq.prompt[:n], self.engine.pool.table(seq.sid)) \
                        if n else None
                except BaseException as e:
                    self._fail_seq(seq, e)      # its own sequence only
                    continue
                sp.attrs["overlapped"] = int(call is not None
                                             and call.overlapped)
            launched.append((seq, call, sp))
        return launched

    def _finish_prefill(self, seq: "_Seq", call, under, epoch: int) -> bool:
        """Wait for a launched prefill and emit its first token, under the
        ``decode.prefill`` span it was launched under (``under``: the
        request's own trace). False once this generation is fenced out.
        Generation by blocks emits nothing yet."""
        L = self.engine.block_length
        try:
            tok = self.engine.finish_prefill(call) \
                if call is not None else None
        except BaseException as e:
            self._fail_seq(seq, e)
            return True
        with _telemetry.span("decode.emit", parent=under,
                             tokens=int(L == 1)):
            with self._cond:
                if self._epoch != epoch:
                    return False
                if seq.state != _S_RUNNING:
                    return True     # retired since its launch (stop())
                seq.prefilled = True
                if L == 1:
                    self._emit_locked(seq, tok)
                else:
                    seq.pos = len(seq.prompt) // L * L
                    self._start_block_locked(seq)
        return True

    def _fail_seq(self, seq: "_Seq", error: BaseException):
        """Fail one sequence on an error of its own prefill; what is no
        ``Exception`` (WorkerKilled et al) goes on to end the thread."""
        with self._cond:
            self._fail_seq_locked(seq, error)
        if not isinstance(error, Exception):
            raise error

    def _admit_locked(self) -> List[_Seq]:    # mxlint: disable=CONC200
        """EDF admission: pull waiting sequences into free batch slots,
        most-negative slack first, reserving their whole KV budget. A
        sequence the pool cannot host yet stays queued (smaller later
        arrivals may still fit — no head-of-line blocking)."""
        free = self.engine.max_batch_size - len(self._active)
        if free <= 0 or not self._waiting:
            return []
        now = _now_us()
        rows = max(1, len(self._active))
        bucket = rows if rows in self.engine.decode_buckets else \
            self.engine.decode_buckets[-1]
        for b in self.engine.decode_buckets:
            if rows <= b:
                bucket = b
                break
        per_tok = self.engine.step_cost.estimate(bucket) / max(1, rows)
        ordered = sorted(self._waiting, key=lambda s: self._slack(s, now,
                                                                  per_tok))
        admits: List[_Seq] = []
        for seq in ordered:
            if len(admits) >= free:
                break
            try:
                self.engine.pool.reserve(seq.sid,
                                         len(seq.prompt) + seq.max_new)
            except KVPoolExhausted:
                continue                # stays queued; retried next step
            self._waiting.remove(seq)
            seq.state = _S_RUNNING
            self._active.append(seq)
            self._stats.seq_event("admitted")
            if not seq.admitted:
                seq.admitted = True
                self._stats.record_queue_wait(now - seq.enqueue_us)
            admits.append(seq)
        self._stats.set_queue_depth(len(self._waiting))
        return admits

    def _slack(self, seq: _Seq, now: int, per_tok_us: float) -> float:
        """EDF key: time remaining until the sequence's next token misses
        its tenant's inter-token SLO, minus the predicted cost of producing
        it. A requeued sequence's deadline anchors on its last emitted
        token; a fresh one on its enqueue time."""
        anchor = seq.last_token_us or seq.enqueue_us
        slo = seq.tenant.slo_us or 1e9      # SLO-less: FIFO by anchor
        return (anchor + slo) - now - per_tok_us

    # ------------------------------------------------------------------
    # emission / retirement (caller holds self._cond)
    # ------------------------------------------------------------------
    def _emit_locked(self, seq: _Seq, tok: int,    # mxlint: disable=CONC200
                     step: Optional[int] = None,
                     confidence: Optional[float] = None):
        now = _now_us()
        seq.emitted.append(tok)
        self._stats.tokens(1)
        if len(seq.emitted) == 1:
            self._stats.record_ttft(now - seq.enqueue_us)
        if seq.last_token_us:
            self._stats.record_intertoken(seq.tenant.name,
                                          now - seq.last_token_us)
        seq.last_token_us = now
        # one token a step has no step to record: ``put(tok)`` as ever
        delivered = seq.stream.put(tok) if step is None \
            else seq.stream.put(tok, step, confidence)
        if seq.stream.cancelled:
            self._retire_locked(seq, _S_CANCELLED, "cancelled")
            return
        if (seq.eos_id is not None and tok == seq.eos_id) \
                or len(seq.emitted) >= seq.max_new:
            self._retire_locked(seq, _S_DONE, "finished")
            return
        if not delivered and seq.state == _S_RUNNING:
            seq.state = _S_PAUSED
            self._stats.seq_event("paused")
            self._stats.backpressure()

    def _retire_locked(self, seq: _Seq, state: str,    # mxlint: disable=CONC200
                       event: str, error: Optional[BaseException] = None):
        seq.state = state
        if seq in self._active:
            self._active.remove(seq)
        self.engine.pool.free(seq.sid)
        self._by_sid.pop(seq.sid, None)
        seq.stream.close(error)
        self._stats.seq_event(event)

    def _fail_seq_locked(self, seq: _Seq,    # mxlint: disable=CONC200
                         error: BaseException):
        if seq in self._waiting:
            self._waiting.remove(seq)
        self._retire_locked(seq, _S_FAILED, "failed", error=error)

    # ------------------------------------------------------------------
    # supervision (monitor thread)
    # ------------------------------------------------------------------
    def _monitor_loop(self):
        while not self._monitor_stop.wait(self._poll_s):
            try:
                self._check_worker()
            except Exception:
                pass        # supervision must outlive any single bad poll

    def _check_worker(self):
        report = None
        with self._cond:
            if self._state == _STOPPED:
                return
            t = self._thread
            if t is None or t.is_alive():
                return
            candidates = [s for s in self._active if s.state == _S_RUNNING]
            requeued, shed = [], 0
            for seq in candidates:
                self._active.remove(seq)
                # a failover requeue IS a retry of this sequence's remaining
                # tokens: it must win a decode-tier budget token, so a
                # crash-looping worker converts into bounded shed instead of
                # requeueing the same sequences forever
                if not _tailguard.retry_allowed("decode"):
                    self._retire_locked(seq, _S_FAILED, "failed",
                                        error=ServerClosedError(
                                            f"sequence {seq.sid} shed: decode "
                                            "retry budget exhausted during "
                                            "worker failover"))
                    shed += 1
                    continue
                seq.state = _S_WAITING
                self._waiting.appendleft(seq)
                self._stats.seq_event("requeued")
                requeued.append(seq)
            report = {
                "endpoint": self.engine.name,
                "reason": "worker_dead",
                "requeued": len(requeued),
                "shed": shed,
                "paused_kept": len(self._active),
                "epoch": self._epoch,
            }
            self.reports.append(report)
            self._stats.failover("worker_dead")
            self._spawn_worker_locked()
        _telemetry.event("decode_failover", **report)
        _flight.trigger("decode_failover", **report)

    @property
    def failovers(self) -> int:
        with self._cond:
            return len(self.reports)

    # ------------------------------------------------------------------
    def snapshot(self) -> Dict:
        with self._cond:
            return {
                "state": self._state,
                "epoch": self._epoch,
                "waiting": len(self._waiting),
                "running": sum(1 for s in self._active
                               if s.state == _S_RUNNING),
                "paused": sum(1 for s in self._active
                              if s.state == _S_PAUSED),
                "tenants": {n: t.slo_us / 1000.0
                            for n, t in self._tenants.items()},
                "failovers": len(self.reports),
            }
