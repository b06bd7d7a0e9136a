"""DLRM-shaped training step over a vocab-sharded embedding table.

The step is the embedding subsystem's proof of life: bottom MLP over the
dense features ⊕ sharded-embedding feature interactions ⊕ top MLP over the
concatenated pair products — the standard DLRM factorization — trained with
plain SGD so the tier-1 oracle can pin the sharded path bitwise against a
single-device dense reference.

Two batch modes, matching the two lookup kernels in table.py:

  ``replicated``   the index batch is replicated over the mesh axis; lookup
                   is masked-local-gather + psum and the row gradients are
                   applied with a shard-local scatter-add. This is the
                   bitwise-oracle path: every float op happens in the same
                   positional order as the dense single-device reference.
  ``sharded``      the batch is sharded over the axis (each shard feeds its
                   own slice); the WHOLE step body runs in one shard_map —
                   per-shard dedup, ``all_to_all`` index dispatch / row
                   return, local MLP forward/backward, ``pmean`` of the MLP
                   gradients, and the reverse ``all_to_all`` routing each
                   shard's (1/n-scaled) row gradients back to their owners.

In both modes the sparse update never leaves the mesh: there is no KVStore
push/pull anywhere in the step (the zero-host-traffic test pins the KVStore
byte counters flat while ``mxtpu_emb_exchange_bytes_total`` moves).

Gradients w.r.t. the table are taken against the *gathered rows* (a closure
differentiated with ``argnums``), never through the collective exchange and
never materializing a dense (V, D) cotangent — RowSparse semantics with the
rows staying on device.

The host wrapper runs each attempt under the resilience stack: the
``emb_dispatch`` fault site fires before the compiled step is entered, so a
retried attempt replays the identical functional step (weights are inputs,
not donated) and converges bitwise with the fault-free run — the property
``tools/chaos_check.py --scenario dlrm`` pins.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as onp

from ..base import MXNetError
from ..resilience import faults as _faults
from .table import ShardedEmbedding, dedup_ids

__all__ = ["DLRMTrainStep", "init_mlp_params", "dlrm_forward", "bce_loss",
           "synthetic_dlrm_batches"]


# ----------------------------------------------------------------------
# model math (shared with gluon.model_zoo.dlrm so serving and training
# agree on the factorization)
# ----------------------------------------------------------------------
def init_mlp_params(dense_in: int, n_fields: int, embed_dim: int,
                    bot_hidden: int = 64, top_hidden: int = 64,
                    seed: int = 0) -> Dict[str, onp.ndarray]:
    """Host-side float32 MLP parameters for the DLRM tower pair."""
    rng = onp.random.RandomState(seed)
    n_pairs = (n_fields + 1) * n_fields // 2
    top_in = embed_dim + n_pairs

    def lin(fan_in, fan_out):
        w = rng.normal(0.0, 1.0 / onp.sqrt(fan_in),
                       (fan_in, fan_out)).astype(onp.float32)
        return w, onp.zeros(fan_out, onp.float32)

    p = {}
    p["w_bot1"], p["b_bot1"] = lin(dense_in, bot_hidden)
    p["w_bot2"], p["b_bot2"] = lin(bot_hidden, embed_dim)
    p["w_top1"], p["b_top1"] = lin(top_in, top_hidden)
    p["w_top2"], p["b_top2"] = lin(top_hidden, 1)
    return p


def dlrm_forward(jnp, mlp, dense, emb_rows):
    """Pure DLRM forward: ``(B, d_in)`` dense + ``(B, F, D)`` embedding rows
    -> ``(B,)`` logits. Bottom MLP, pairwise dot interactions over the F+1
    feature vectors (lower triangle, diagonal excluded), top MLP."""
    bot = jnp.maximum(dense @ mlp["w_bot1"] + mlp["b_bot1"], 0)
    bot = jnp.maximum(bot @ mlp["w_bot2"] + mlp["b_bot2"], 0)      # (B, D)
    z = jnp.concatenate([bot[:, None, :], emb_rows], axis=1)       # (B, F+1, D)
    zz = jnp.einsum("bij,bkj->bik", z, z)                          # (B,F+1,F+1)
    li, lj = onp.tril_indices(z.shape[1], k=-1)
    inter = zz[:, li, lj]                                          # (B, pairs)
    top = jnp.concatenate([bot, inter], axis=1)
    h = jnp.maximum(top @ mlp["w_top1"] + mlp["b_top1"], 0)
    return (h @ mlp["w_top2"] + mlp["b_top2"])[:, 0]


def bce_loss(jnp, logit, y):
    """Sigmoid BCE with logits: mean(softplus(x) - y*x)."""
    return jnp.mean(jnp.logaddexp(0.0, logit) - y * logit)


def synthetic_dlrm_batches(n_batches: int, batch: int, dense_in: int,
                           n_fields: int, vocab: int, seed: int = 0,
                           hot_frac: float = 0.7):
    """Deterministic synthetic DLRM data (bench / chaos / tests): dense
    normals, skewed sparse ids (``hot_frac`` of lookups land in the first
    vocab/16 rows — the hot head a frequency-sorted vocab would have), and
    Bernoulli labels. Returns a list of host (dense, idx, y) tuples."""
    rng = onp.random.RandomState(seed)
    head = max(1, vocab // 16)
    out = []
    for _ in range(n_batches):
        dense = rng.normal(0, 1, (batch, dense_in)).astype(onp.float32)
        hot = rng.randint(0, head, (batch, n_fields))
        cold = rng.randint(0, vocab, (batch, n_fields))
        pick = rng.uniform(size=(batch, n_fields)) < hot_frac
        idx = onp.where(pick, hot, cold).astype(onp.int32)
        y = (rng.uniform(size=batch) < 0.5).astype(onp.float32)
        out.append((dense, idx, y))
    return out


# ----------------------------------------------------------------------
# the train step
# ----------------------------------------------------------------------
class DLRMTrainStep:
    """SGD train step for the DLRM workload over a ShardedEmbedding.

    Parameters
    ----------
    table : ShardedEmbedding
        The sparse feature table (owns mesh/axis/placement).
    dense_in, n_fields : int
        Dense feature width and number of sparse fields per example.
    bot_hidden, top_hidden : int
        MLP widths.
    lr : float
        Plain SGD rate (no momentum/wd — the oracle pins ``w + (-lr*g)``).
    mode : str
        ``replicated`` (bitwise-oracle path) or ``sharded`` (all_to_all
        dispatch path; requires a partitioned table with > 1 shard).
    retry : resilience.RetryPolicy, optional
        Attempts run under this policy at fault site ``emb_dispatch``.
    """

    def __init__(self, table: ShardedEmbedding, dense_in: int, n_fields: int,
                 bot_hidden: int = 64, top_hidden: int = 64, lr: float = 0.1,
                 mode: str = "replicated", seed: int = 0, retry=None):
        import jax
        if mode not in ("replicated", "sharded"):
            raise MXNetError(f"unknown DLRM step mode {mode!r}")
        if mode == "sharded" and (table.placement != "partition"
                                  or table.n_shards <= 1):
            mode = "replicated"   # degenerate mesh: the paths coincide
        self.table = table
        self.dense_in = int(dense_in)
        self.n_fields = int(n_fields)
        self.lr = float(lr)
        self.mode = mode
        self._retry = retry
        self._t = 0
        host = init_mlp_params(dense_in, n_fields, table.embed_dim,
                               bot_hidden, top_hidden, seed)
        rep = table.mesh.replicated()
        self._mlp = {k: jax.device_put(v, rep) for k, v in host.items()}
        self._step = (self._build_replicated() if mode == "replicated"
                      else self._build_sharded())

    # -- compiled bodies -----------------------------------------------
    def _build_replicated(self):
        import jax
        import jax.numpy as jnp
        gather = self.table.gather_fn()
        scatter = self.table.scatter_add_fn()
        lr = self.lr

        def step(tbl, mlp, dense, uniq, inv, y):
            rows = gather(tbl, uniq)

            def fwd(mlp, rows):
                logit = dlrm_forward(jnp, mlp, dense, rows[inv])
                return bce_loss(jnp, logit, y)

            loss, (g_mlp, g_rows) = jax.value_and_grad(
                fwd, argnums=(0, 1))(mlp, rows)
            tbl = scatter(tbl, uniq, (-lr) * g_rows)
            mlp = jax.tree_util.tree_map(lambda w, g: w - lr * g, mlp, g_mlp)
            return tbl, mlp, loss

        return jax.jit(step)

    def _build_sharded(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from ..parallel import collectives
        t = self.table
        axis, n, pv, lr = t.axis, t.n_shards, t.padded_vocab, self.lr

        def _local(tbl, mlp, dense, idx, y):
            flat = idx.reshape(-1).astype(jnp.int32)
            uniq, inv = jnp.unique(flat, return_inverse=True,
                                   size=flat.shape[0], fill_value=pv)
            uniq = uniq.astype(jnp.int32)
            inv = inv.reshape(idx.shape)
            # dispatch: offer this shard's unique ids to every owner
            send = jnp.broadcast_to(uniq[None, :], (n, uniq.shape[0]))
            recv = collectives.all_to_all(send, axis, 0, 0)
            local, ok = t._owner_local(jnp, recv.reshape(-1))
            rows = jnp.where(ok[:, None],
                             tbl.at[local].get(mode="fill", fill_value=0), 0)
            rows = rows.reshape(n, uniq.shape[0], -1)
            rows = collectives.all_to_all(rows, axis, 0, 0).sum(0)

            def fwd(mlp, rows):
                logit = dlrm_forward(jnp, mlp, dense, rows[inv])
                return bce_loss(jnp, logit, y)

            loss, (g_mlp, g_rows) = jax.value_and_grad(
                fwd, argnums=(0, 1))(mlp, rows)
            # global grad = pmean of per-shard grads (equal local batches)
            g_mlp = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, axis), g_mlp)
            # reverse dispatch: each shard's 1/n-scaled row grads go home
            upd = (-lr / n) * g_rows
            send_upd = jnp.broadcast_to(upd[None], (n,) + upd.shape)
            recv_ids = collectives.all_to_all(send, axis, 0, 0)
            recv_upd = collectives.all_to_all(send_upd, axis, 0, 0)
            loc2, _ = t._owner_local(jnp, recv_ids.reshape(-1))
            tbl = tbl.at[loc2].add(
                recv_upd.reshape(-1, upd.shape[-1]).astype(tbl.dtype),
                mode="drop")
            mlp = jax.tree_util.tree_map(lambda w, g: w - lr * g, mlp, g_mlp)
            return tbl, mlp, jax.lax.pmean(loss, axis)

        wrapped = jax.shard_map(
            _local, mesh=t.mesh.mesh,
            in_specs=(P(axis, None), P(), P(axis), P(axis), P(axis)),
            out_specs=(P(axis, None), P(), P()), check_vma=False)
        return jax.jit(wrapped)

    # -- host surface ---------------------------------------------------
    def stage(self, batch):
        """Device-stage one host ``(dense, idx, y)`` batch: the DeviceFeed
        ``stage`` hook. Replicated mode pre-dedups the index bundle through
        the shared jitted kernel; sharded mode places the batch slices
        under their batch sharding."""
        import jax
        dense, idx, y = batch
        dense = onp.ascontiguousarray(dense, onp.float32)
        y = onp.ascontiguousarray(y, onp.float32)
        mesh = self.table.mesh
        if self.mode == "replicated":
            rep = mesh.replicated()
            uniq, inv = dedup_ids(onp.ascontiguousarray(idx, onp.int32),
                                  self.table.padded_vocab)
            return {"dense": jax.device_put(dense, rep), "uniq": uniq,
                    "inv": inv, "y": jax.device_put(y, rep),
                    "n_ids": int(uniq.shape[0])}
        sh = mesh.sharding(self.table.axis)
        idx = onp.ascontiguousarray(idx, onp.int32)
        return {"dense": jax.device_put(dense, sh),
                "idx": jax.device_put(idx, sh),
                "y": jax.device_put(y, sh), "n_ids": int(idx.size)}

    def __call__(self, batch, idx=None, y=None):
        """Run one step; accepts a raw host ``(dense, idx, y)`` tuple (or
        three positional arrays), or a bundle already staged by
        :meth:`stage`. Returns the scalar loss."""
        if idx is not None:
            batch = (batch, idx, y)
        if not isinstance(batch, dict):
            batch = self.stage(batch)

        def attempt():
            _faults.check("emb_dispatch")
            if self.mode == "replicated":
                return self._step(self.table.weight, self._mlp,
                                  batch["dense"], batch["uniq"],
                                  batch["inv"], batch["y"])
            return self._step(self.table.weight, self._mlp,
                              batch["dense"], batch["idx"], batch["y"])

        if self._retry is not None:
            tbl, mlp, loss = self._retry.run(attempt, site="emb_dispatch")
        else:
            tbl, mlp, loss = attempt()
        self.table._weight = tbl
        self._mlp = mlp
        self._t += 1
        self.table.record_exchange(batch["n_ids"],
                                   dispatch=(self.mode == "sharded"))
        return float(loss)

    @property
    def mlp(self):
        return self._mlp

    # -- checkpoint surface (resilience.CheckpointManager glue) ---------
    def state_dict(self) -> Dict:
        """Gathered host snapshot. The table is saved in STORED layout plus
        its geometry, so a restore onto a different shard count/layout
        (elastic) can rebuild the logical rows exactly."""
        import jax
        t = self.table
        return {"kind": "DLRMTrainStep", "version": 1, "t": int(self._t),
                "table_vocab": t.vocab_size, "table_dim": t.embed_dim,
                "table_shards": t.n_shards, "table_rps": t.rows_per_shard,
                "table_layout": t.layout,
                "table": onp.asarray(jax.device_get(t.weight)),
                "mlp": {k: onp.asarray(jax.device_get(v))
                        for k, v in self._mlp.items()}}

    def shard_state_dict(self) -> Dict:
        """Sharded twin: on-mesh leaves captured as per-device shards
        (``resilience.sharding.ShardedLeaf``) — no host ever materializes
        the full table."""
        from ..resilience.sharding import ShardedLeaf
        devpos = self.table.mesh.device_positions()

        def cap(a):
            if hasattr(a, "addressable_shards"):
                return ShardedLeaf.from_array(a, devpos)
            return onp.asarray(a)

        st = self.state_dict()
        st["table"] = cap(self.table.weight)
        st["mlp"] = {k: cap(v) for k, v in self._mlp.items()}
        return st

    def load_state_dict(self, state: Dict):
        """Restore from an assembled snapshot, re-sharding onto THIS step's
        mesh — the saving mesh's shard count/layout may differ (elastic
        4-way→1-way restore rides this)."""
        import jax
        if state.get("kind") != "DLRMTrainStep":
            raise MXNetError(
                f"not a DLRMTrainStep state: {state.get('kind')!r}")
        vocab = int(state["table_vocab"])
        if vocab != self.table.vocab_size:
            raise MXNetError(f"table vocab {vocab} != {self.table.vocab_size}")
        stored = onp.asarray(state["table"])
        rps, n = int(state["table_rps"]), int(state["table_shards"])
        ids = onp.arange(vocab)
        sidx = ids if state["table_layout"] == "block" \
            else (ids % n) * rps + ids // n
        self.table.set_weight(stored[sidx])
        rep = self.table.mesh.replicated()
        self._mlp = {k: jax.device_put(onp.asarray(v), rep)
                     for k, v in dict(state["mlp"]).items()}
        self._t = int(state["t"])

    def __repr__(self):
        return (f"DLRMTrainStep(mode={self.mode}, t={self._t}, "
                f"table={self.table!r})")
