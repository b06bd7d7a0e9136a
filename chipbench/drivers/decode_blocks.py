"""Closed-loop decode traffic for a model generated by diffusion over blocks:
``decode_closed``'s loop, window and check unchanged (the family's
``check_requests`` decides ``correct``; the cell's ``generate`` group carries
``denoising_steps``), with what the readers of such a run need beside it.

A step forwards ``block_length`` rows a lane and places 0 to ``block_length``
tokens in it, so the run's ``max_batch_size`` is given in rows a forward
(lanes x ``block_length``): ``batch_occupancy_pct.decode`` stays the share of
forwarded rows that became a token a client received. Prompts draw their ids
from the vocabulary below the mask token, which is its last id.
"""
import numpy as onp

from . import decode_closed

END_TO_END = decode_closed.END_TO_END
KIND = decode_closed.KIND


def run(bench):
    cell, config = bench.cell, bench.config
    family = bench.family()
    draw = decode_closed.make_requests
    if config["mask_token_id"] != config["vocab_size"] - 1:
        raise SystemExit("decode_blocks: the mask token has to be the "
                         "vocabulary's last id, so that prompts draw below it")
    decode_closed.make_requests = lambda cell, vocab, seed: draw(
        cell, config["mask_token_id"], seed)
    try:
        run = decode_closed.run(bench)
    finally:
        decode_closed.make_requests = draw
    L = config["block_length"]
    rows = cell["max_batch_size"] * L
    # a lane's live context over its answer, on average: the prompt and half
    # the answer (the sizes are the pool's, whatever the seed)
    context = float(onp.mean([len(p) + b / 2 for p, b in
                              draw(cell, config["mask_token_id"], 0)]))
    run.update(
        max_batch_size=rows, rows_per_forward=rows, block_length=L,
        denoising_steps=cell["generate"]["denoising_steps"],
        device_kind=bench.device_row["device_kind"], chips=bench.chips,
        tokens_per_s=run["end_to_end"]["decode_tokens_per_s"],
        # the window is ``--seconds`` long to a millisecond
        tokens_in_window=run["end_to_end"]["decode_tokens_per_s"]
        * bench.seconds,
        mean_context=context,
        layers=config["num_hidden_layers"],
        flops_per_token=family.forward_flops(config, 1, context),
        forward_flops=family.forward_flops(config, rows, context),
        forward_bytes=family.forward_bytes(config, rows, context),
        expert_flops=family.expert_flops(config, rows),
        expert_bytes=family.expert_bytes(config, rows),
        expert_ops=family.expert_ops(config, rows))
    return run
