"""Closed-loop decode traffic for a model that caches a latent and routes
experts: ``decode_closed``'s loop, window and check unchanged (the family's
``check_requests`` decides ``correct``), with what the readers of such a run
need beside it: the family's counts of what a token, a layer's routed product,
the latent attention kernel and the prefill's attention kernel require, the
kernels' names in a trace, and when the traced window stood open on the host's
clock, so that the ``decode.step`` spans of that window can be told from the
rest of the ring without a join of clocks.
"""
import contextlib
import time

import numpy as onp

from . import decode_closed

END_TO_END = decode_closed.END_TO_END
KIND = decode_closed.KIND


def run(bench):
    from mxnet_tpu.serving import bucketing

    cell, config = bench.cell, bench.config
    family = bench.family()
    traced = bench.traced_window
    opened = []

    @contextlib.contextmanager
    def timed_window():
        t0 = time.perf_counter()
        with traced():
            yield
        opened.append((t0, time.perf_counter()))

    bench.traced_window = timed_window
    try:
        run = decode_closed.run(bench)
    finally:
        bench.traced_window = traced
    lanes = cell["max_batch_size"]
    # a lane's live context over its answer, on average: the prompt and half
    # the answer (the sizes are the pool's, whatever the seed)
    context = float(onp.mean([len(p) + b / 2 for p, b in
                              decode_closed.make_requests(
                                  cell, config["vocab_size"], 0)]))
    layers = config["num_hidden_layers"]
    run.update(
        device_kind=bench.device_row["device_kind"], chips=bench.chips,
        tokens_per_s=run["end_to_end"]["decode_tokens_per_s"],
        flops_per_token=family.forward_flops(config, 1, context),
        expert_flops=family.expert_flops(config, lanes),
        expert_bytes=family.expert_bytes(config, lanes),
        expert_ops=family.expert_ops(config, lanes),
        # the latent attention kernel at the full bucket, and what a cached
        # position costs it over all layers
        latent_attention_op=family.latent_attention_op(config, lanes),
        latent_flops_per_position=layers
        * family.latent_attention_flops(config, 1),
        latent_bytes_per_position=layers
        * family.latent_attention_bytes(config, 1),
        # the prefill's attention kernel by rung: {name: (FLOPs, bytes)}
        prefill_attention_ops={
            label: (family.prefill_attention_flops(config, rows),
                    family.prefill_attention_bytes(config, rows))
            for label, rows in family.prefill_attention_ops(
                config, bucketing.seq_buckets(cell["max_seq_len"])).items()},
        traced_window_host_s=opened[0] if opened else None)
    return run
