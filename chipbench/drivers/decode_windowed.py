"""Closed-loop decode traffic for a model whose layers keep different spans of
the cache (window and full attention layers over a pool that gives each kind
its own pages) and route experts: ``decode_closed``'s loop, window and check
unchanged (the family's ``check_requests`` decides ``correct``), with what the
readers of such a run need beside it: the family's counts of what a token, a
layer's routed product, the paged attention kernel and the prefill's attention
kernel require, the kernels' names in a trace, the layers of each kind, and
when the traced window stood open on the host's clock, so that the
``decode.step`` spans of that window (``ctx_live``, ``ctx_window_live``) can
be told from the rest of the ring without a join of clocks. It also keeps
hold of the endpoint the loop builds, to say after the run what the pool's
groups held: ``correct`` takes that no sequence ever held more pages of a
window group than its ring.
"""
import contextlib
import time

import numpy as onp

from . import decode_closed

END_TO_END = decode_closed.END_TO_END
KIND = decode_closed.KIND


def run(bench):
    from mxnet_tpu import serving
    from mxnet_tpu.serving import bucketing

    cell, config = bench.cell, bench.config
    family = bench.family()
    traced = bench.traced_window
    opened, endpoints = [], []
    endpoint = serving.DecodeEndpoint

    class Kept(endpoint):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            endpoints.append(self)

    @contextlib.contextmanager
    def timed_window():
        t0 = time.perf_counter()
        with traced():
            yield
        opened.append((t0, time.perf_counter()))

    bench.traced_window = timed_window
    serving.DecodeEndpoint = Kept
    try:
        run = decode_closed.run(bench)
    finally:
        bench.traced_window = traced
        serving.DecodeEndpoint = endpoint
    pool = endpoints[0].pool.snapshot()
    bench.say({"kv_pool": pool})
    for group in pool.get("groups", ()):
        if group["window"] is not None:
            held = {"value": group["peak_seq_pages"],
                    "limit": group["pages_per_seq"]}
            run["compared"]["window_pages_a_sequence_peak"] = held
            run["correct"] = run["correct"] and \
                held["value"] <= held["limit"]
    lanes = cell["max_batch_size"]
    # a lane's live context over its answer, on average: the prompt and half
    # the answer (the sizes are the pool's, whatever the seed)
    context = float(onp.mean([len(p) + b / 2 for p, b in
                              decode_closed.make_requests(
                                  cell, config["vocab_size"], 0)]))
    full, window = family.layers_by_kind(config)
    run.update(
        device_kind=bench.device_row["device_kind"], chips=bench.chips,
        tokens_per_s=run["end_to_end"]["decode_tokens_per_s"],
        flops_per_token=family.forward_flops(config, 1, context),
        expert_flops=family.expert_flops(config, lanes),
        expert_bytes=family.expert_bytes(config, lanes),
        expert_ops=family.expert_ops(config, lanes),
        # the paged attention kernel at the full bucket, what a cached
        # position costs one layer of it, and the layers that read every
        # live position and those that read a window of them
        paged_attention_op=family.paged_attention_op(config, lanes),
        paged_flops_per_position=family.paged_attention_flops(config, 1),
        paged_bytes_per_position=family.paged_attention_bytes(config, 1),
        paged_layers={"full": full, "window": window},
        # the prefill's attention kernel by rung: {name: (FLOPs, bytes)}
        prefill_attention_ops={
            label: (family.prefill_attention_flops(config, rows),
                    family.prefill_attention_bytes(config, rows))
            for label, rows in family.prefill_attention_ops(
                config, bucketing.seq_buckets(cell["max_seq_len"])).items()},
        traced_window_host_s=opened[0] if opened else None)
    return run
