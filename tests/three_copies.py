"""A routed pass's rows the way a pass summed them before each row read its
own pairs, kept as the reference of ``tests/test_serving_blocks.py`` and
``tests/test_mla_lm.py``: a weighted copy of the down-projection's float32
result, the copy put back in pair order, each row's pairs summed."""
import jax.numpy as jnp

from mxnet_tpu.ops import nn as ops


def three_copies(x, top_w, top_i, w_gate, w_up, w_down, first=0):
    T, top_k = top_i.shape
    held = w_gate.shape[0]
    expert = top_i.reshape(-1) - first
    here = (expert >= 0) & (expert < held)
    expert = jnp.where(here, expert, held)
    order = jnp.argsort(expert, stable=True)
    sizes = jnp.bincount(expert, length=held + 1)[:held].astype(jnp.int32)
    weight = jnp.where(here, top_w.reshape(-1), 0.0)[order]
    y = ops._expert_pass(x, order, sizes, w_gate, w_up, w_down, top_k)
    live = jnp.arange(len(order)) < sizes.sum()
    y = jnp.where(live[:, None], y * weight[:, None], 0.0)
    return y[jnp.argsort(order)].reshape(T, top_k, -1).sum(1).astype(x.dtype)
