"""mxnet_tpu.serving.generate — autoregressive decode serving.

The generative counterpart of the request/response InferenceServer path:
instead of one device step per request, a sequence costs one *prefill* step
plus one *decode* step per generated token, and the scheduling unit is the
token, not the request.

Four pieces (one module each):

- :class:`PagedKVPool` (kv_cache.py): preallocated on-device K/V block
  pools with per-sequence page tables. Page 0 is a scratch page for masked
  writes; pools ride as executable *arguments*, so the compiled programs
  are independent of pool contents, and a decode step attends to them in
  place (``ops/pallas/paged_attention``).
- :class:`DecodeEndpoint` (engine.py): one generative model (the
  ``TransformerLM`` incremental-decode protocol) with two AOT executable
  families per bucket — prefill (by sequence length, ``seq_buckets``) and
  decode-step (by batch size, pow2) — routed through
  ``compile_ledger.lower_and_compile``.
- :class:`DecodeScheduler` (scheduler.py): token-granularity continuous
  batching — sequences join/retire from the running batch every step, EDF
  admission priced by the live StepCostEWMA against per-tenant inter-token
  SLOs, lossless stream backpressure, graceful drain, and worker failover
  that requeues partial sequences with pages/position/tokens intact.
- :class:`TokenStream` (streams.py): the client half — a bounded blocking
  iterator (or per-token callback) with a resume callback for backpressure.

Numerics contract (tier-1 tested): batched continuous decode is BITWISE
equal to one-sequence-at-a-time greedy decode — including sequences joining
and retiring mid-batch and KV pages being freed and reallocated between
sequences. Every model op is per-row; positions past a row's length are not
read or carry exactly zero softmax weight, so stale page contents, batch
composition, bucket padding and physical page placement are all invisible
to a row's output.

    from mxnet_tpu.serving.generate import DecodeEndpoint, DecodeScheduler

    eng = DecodeEndpoint("lm", TransformerLM(...), max_seq_len=128)
    with DecodeScheduler(eng) as sched:
        stream = sched.submit([1, 2, 3], max_new_tokens=16)
        for tok in stream:
            ...

Or through the server facade: ``server.register_generator(eng)`` then
``server.generate("lm", prompt)``.
"""
from __future__ import annotations

from .engine import DecodeEndpoint
from .kv_cache import PagedKVPool, write_prefill, write_step
from .scheduler import DecodeScheduler
from .stats import DecodeStats
from .streams import TokenStream
from ..errors import KVPoolExhausted

__all__ = ["DecodeEndpoint", "DecodeScheduler", "TokenStream", "PagedKVPool",
           "DecodeStats", "KVPoolExhausted", "write_prefill", "write_step"]
