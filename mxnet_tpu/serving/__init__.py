"""mxnet_tpu.serving — pipelined, multi-tenant dynamic-batching inference.

The production request->response path over this framework (the serving-system
component TensorFlow treats as first-class, PAPERS.md): concurrent client
requests are accumulated by a dynamic batcher into device-sized batches under
a configurable deadline, padded to shape buckets so every bucket hits one
cached compiled executable (never recompiling in steady state), executed as
one device step, and sliced back into per-request responses.

r6 rebuilt the dispatch path into a multi-tenant scheduler with a
double-buffered host pipeline:

- **Router** (router.py): N endpoints multiplex over the single
  device-owning dispatch path; the next batch is picked
  earliest-deadline-first across tenants, priced by each bucket's measured
  step-time EWMA (seeded at warmup), with shortest-job-first among
  already-late tenants so a long batch cannot convoy short requests.
  Batches assemble at the last moment — rows arriving during device step k
  join batch k+1 (continuous batching).
- **Host pipeline** (pipeline.py): a prep thread concat/pads and
  ``device_put``s batch k+1 into the next parity's input-buffer set while
  the worker executes batch k; host time leaves the critical path. Only the
  worker invokes compiled executables. ``InferenceServer(pipeline=False)``
  keeps the serial path (bitwise-identical outputs, same executables).
- **Per-tenant shedding**: each endpoint gets its own CircuitBreaker, so one
  tenant's overload tightens that tenant's admission, not the whole server.

r7 adds the elastic layer: **zero-downtime weight hot-swap**
(``server.hot_swap(name, ckpt)`` verifies + stages off the serving path,
probe-validates bitwise against recorded outputs, cuts over on the worker
at a batch boundary, rolls back on failure) and **worker failover**
(``PoolSupervisor`` declares a dead or watchdog-wedged worker, requeues its
batches front-of-queue with deadlines intact, trips only the affected
tenant's breaker, restarts the worker generation). See RESILIENCE.md's
"Preemption & hot-swap runbook".

    from mxnet_tpu import serving

    ep = serving.ModelEndpoint("resnet50", net, input_shapes=(3, 224, 224),
                               dtype="bfloat16", max_batch_size=32)
    server = serving.InferenceServer(batch_timeout_ms=2.0, max_queue=256)
    server.register(ep, slo_ms=50.0)   # warms buckets + seeds the step costs
    server.start()

    out = server.predict("resnet50", img)           # blocking
    fut = server.submit("resnet50", img, deadline_ms=50.0)  # async w/ deadline

    serving.stats()["resnet50"]  # p50/p95/p99, queue_wait, prep, shed, ...
    server.stop(drain=True)      # graceful: flushes admitted work first

Numerics contract: a served output is BITWISE equal to the hybridized direct
forward of the same rows — the endpoint executable is the same
single-XLA-computation trace CachedOp builds, padding rows never mix into
real rows, and bucket size does not change per-row results; the pipelined
path reuses the serial path's executables, padding and concat, so it is
bitwise-identical to serial serving too. (Eager op-by-op dispatch of the
same net may differ by float rounding, because XLA fuses the whole traced
graph differently than per-op programs.)

Robustness contract: the queue is bounded per tenant (ServerOverloadError at
admission — explicit backpressure instead of unbounded latency), per-request
deadlines drop expired work before it occupies device rows
(RequestTimeoutError), and shutdown drains by default with a bounded timeout
(abandoned requests are failed, never waited on forever). Each device batch
step runs under a resilience.RetryPolicy (transient failures retried within
the batch's earliest deadline), a Watchdog flags hung steps (degrading the
stalled tenant's breaker), and per-tenant CircuitBreakers shed load
(HEALTHY→DEGRADED→OPEN→HALF_OPEN) — see ``InferenceServer.health()`` and
RESILIENCE.md. Observability rides the telemetry registry: queue-wait and
prep histograms, the prep/step overlap gauge, per-tenant shed counters, and
``stats()`` snapshots per-endpoint latency histograms, queue depth, batch
occupancy (real vs padded rows) and executable-cache hit/compile counters.

r11 adds the generative path (``serving.generate``): autoregressive decode
with a paged KV cache and token-granularity continuous batching — a
``DecodeEndpoint`` compiles two AOT executables per bucket (prefill by
sequence length, decode-step by batch size), a ``DecodeScheduler`` re-forms
the decode batch every token (EDF admission against per-tenant *inter-token*
SLOs, lossless stream backpressure, failover that requeues partial
sequences), and ``server.register_generator(engine)`` /
``server.generate(name, prompt)`` expose it behind the InferenceServer
facade with streaming ``TokenStream`` responses. Batched continuous decode
is bitwise-equal to serial greedy decode (tier-1 oracle).

r16 adds the serving fabric (``serving.fabric``): mesh-sharded replicas and
a multi-host front door. ``plan_slices`` carves the visible device set into
gang-scheduled slices; a ``ShardedEndpoint`` / ``ShardedDecodeEndpoint``
spans one slice's mesh with NamedSharding-compiled bucket executables
(bitwise-equal to the single-chip twins; same executable cache, compile
ledger and warmup contracts), ``ServingPool.submit`` weights placement by
replica capacity, and ``FrontDoor`` adds consistent-hash tenant→host
routing with bounded rebalancing plus cross-host failover that replays a
dead host's in-flight work on survivors — zero client-visible errors.

r18 adds the tail-tolerance defense layer (``serving.tailguard``): one
end-to-end ``Deadline`` minted at ingress rides every hop and fails fast
(``DeadlineExceeded``, which ``RequestTimeoutError`` now derives from);
``ServingPool.submit`` hedges a late request onto the second-least-loaded
replica under a token-bucket hedge budget (first response wins, loser
cancelled at batch assembly, results bitwise-equal to unhedged); per-tier
retry budgets (frontdoor / execute / decode) convert retry storms into
bounded shed; and a ``BrownoutController`` ladder degrades under sustained
SLO burn in tenant-criticality order (``register(..., tier="bulk")`` sheds
before silver before gold; gold is never refused).
"""
from __future__ import annotations

from .autoscaler import Autoscaler, ServingPool
from .endpoint import ModelEndpoint, get_endpoint, list_endpoints, unregister
from .errors import (DeadlineExceeded, HotSwapError, KVPoolExhausted,
                     RequestTimeoutError, ServerClosedError,
                     ServerOverloadError, ServingError)
from .router import Router, StepCostEWMA, Tenant
from .server import InferenceServer
from .supervisor import PoolSupervisor
from . import bucketing
from . import generate
from .generate import (DecodeEndpoint, DecodeScheduler, PagedKVPool,
                       TokenStream)
from . import fabric
from .fabric import (FrontDoor, ShardedDecodeEndpoint, ShardedEndpoint,
                     SliceSpec, plan_slices)
from . import tailguard
from .tailguard import (BROWNOUT, BrownoutController, Deadline, HEDGER,
                        HedgePolicy, RETRY_BUDGETS, RetryBudgets, TIER_RANKS,
                        TokenBucket)

__all__ = ["ModelEndpoint", "InferenceServer", "PoolSupervisor", "stats",
           "get_endpoint", "list_endpoints", "unregister", "ServingError",
           "ServerOverloadError", "RequestTimeoutError", "ServerClosedError",
           "HotSwapError", "KVPoolExhausted", "DeadlineExceeded", "Router",
           "StepCostEWMA", "Tenant", "bucketing", "generate",
           "DecodeEndpoint", "DecodeScheduler", "PagedKVPool", "TokenStream",
           "ServingPool", "Autoscaler", "fabric", "FrontDoor",
           "ShardedEndpoint", "ShardedDecodeEndpoint", "SliceSpec",
           "plan_slices", "tailguard", "Deadline", "TokenBucket",
           "RetryBudgets", "RETRY_BUDGETS", "HedgePolicy", "HEDGER",
           "BrownoutController", "BROWNOUT", "TIER_RANKS"]


def stats():
    """Snapshot of every registered endpoint's serving metrics:
    ``{endpoint: {counters, queue_depth, batch_occupancy, latency, step,
    queue_wait, prep, shed}}``. Latency blocks carry
    count/mean/p50/p95/p99/min/max in microseconds."""
    from .endpoint import _ENDPOINTS, _REG_LOCK
    with _REG_LOCK:
        eps = list(_ENDPOINTS.values())
    return {ep.name: ep.stats.snapshot() for ep in eps}
