"""BERT model family (the BASELINE.json "BERT-base pretraining" config).

The reference carries the *ops* for BERT — fused interleaved attention matmuls
(src/operator/contrib/transformer.cc:650-828), masked softmax
(nn/softmax-inl.h:682-733), LayerNorm — while the model itself lives downstream
in GluonNLP. Here the model is part of the model zoo so the benchmark config is
self-contained.

TPU-native design: every sub-block is a HybridBlock, so the whole pretraining
step traces into ONE XLA computation. Attention uses a single fused QKV
projection (the interleaved_matmul_selfatt design) so the MXU sees one big
matmul. `shard_for_tensor_parallel` annotates the weights with PartitionSpecs
(Megatron-style: QKV/FFN-in column-parallel, proj/FFN-out row-parallel) for
ParallelTrainStep; sequence parallelism comes from sharding the sequence axis
of the inputs (sp) and, for long contexts, parallel.ring_attention.
"""
from __future__ import annotations

import math

from ..block import HybridBlock
from ..nn import Dense, Dropout, Embedding, HybridSequential, LayerNorm

__all__ = ["BERTEncoder", "BERTModel", "BERTForPretraining", "BERTPretrainingLoss",
           "TransformerLM", "bert_base", "bert_large",
           "shard_for_tensor_parallel"]


class SelfAttention(HybridBlock):
    """Multi-head self-attention with fused QKV (contrib/transformer.cc:650
    interleaved_matmul_selfatt_qk/valatt semantics, one projection matmul).

    ``causal=True`` bakes the bottom-right causal mask into attention
    (decoder-only stacks — TransformerLM); besides the full forward the block
    then offers the two incremental-decode views the generative-serving
    engine compiles: ``forward_collect`` (prefill: full causal pass that also
    returns the per-position K/V for the cache) and ``attend_step`` (one
    token against its cached context, read in the paged KV pool by
    ``paged_attention``)."""

    def __init__(self, units, num_heads, dropout=0.0, causal=False, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._heads = num_heads
        self._causal = causal
        with self.name_scope():
            self.qkv = Dense(3 * units, flatten=False, in_units=units)
            self.proj = Dense(units, flatten=False, in_units=units)
            self.drop = Dropout(dropout)

    def _project(self, F, x):
        qkv = self.qkv(x)
        q = F.slice_axis(qkv, axis=-1, begin=0, end=self._units)
        k = F.slice_axis(qkv, axis=-1, begin=self._units, end=2 * self._units)
        v = F.slice_axis(qkv, axis=-1, begin=2 * self._units, end=3 * self._units)
        return q, k, v

    def hybrid_forward(self, F, x, mask=None):
        q, k, v = self._project(F, x)
        out = F.multi_head_attention(q, k, v, mask, heads=self._heads,
                                     causal=self._causal)
        return self.drop(self.proj(out))

    def forward_collect(self, x, mask=None):
        """Full forward that also returns the (B, S, H*D) key/value
        projections — the prefill half of the KV-cache contract."""
        F = _F()
        q, k, v = self._project(F, x)
        out = F.multi_head_attention(q, k, v, mask, heads=self._heads,
                                     causal=self._causal)
        return self.drop(self.proj(out)), k, v

    def attend_step(self, x, lengths, k_pool, v_pool, tables, layer):
        """One decode step: ``x`` (B, H*D) is the current token's hidden
        state, ``lengths`` (B,) the number of cached positions per row (==
        the current token's position), ``k_pool``/``v_pool`` the whole paged
        pools, of which this block reads ``layer`` through the rows' page
        ``tables`` (B, P). The token attends to its cached context in place
        (``paged_attention``) and to itself, the two parts merged under one
        softmax. Returns (out, k_new, v_new) so the caller can append this
        step's K/V to the cache."""
        F = _F()
        q, k, v = self._project(F, x)
        row = q.expand_dims(1)          # a block of one row a lane
        ctx = F.paged_attention(row, k_pool, v_pool, tables, lengths, layer,
                                heads=self._heads)
        out = F.block_attention(
            row, k.expand_dims(1), v.expand_dims(1), lengths.expand_dims(1),
            *ctx, heads=self._heads, kv_heads=self._heads).reshape(x.shape)
        return self.drop(self.proj(out)), k, v


class PositionwiseFFN(HybridBlock):
    """FFN with the original-BERT tanh GELU (google-research/bert
    modeling.py gelu) as default: numerically ~1e-3 of the erf-exact form
    and measured 17% faster end-to-end on v5e (PERF.md round 5 — the erf
    VJP forces an extra saved pre-activation tensor through the MLP matmul
    fusions). Pass activation="gelu" for the erf-exact variant — e.g. when
    fine-tuning checkpoints trained against the reference framework's
    erf-GELU op (default changed in round 5, see CHANGELOG.md)."""

    def __init__(self, units, hidden_size, dropout=0.0, activation="gelu_tanh",
                 **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ffn1 = Dense(hidden_size, flatten=False, in_units=units)
            self.ffn2 = Dense(units, flatten=False, in_units=hidden_size)
            self.drop = Dropout(dropout)
        self._act = activation

    def forward(self, x):
        F = _F()
        h = self.ffn1(x)
        h = getattr(F, self._act)(h)
        return self.drop(self.ffn2(h))


class TransformerEncoderLayer(HybridBlock):
    """Post-LN transformer encoder layer (BERT convention)."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 activation="gelu_tanh", causal=False, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attention = SelfAttention(units, num_heads, dropout,
                                           causal=causal)
            self.ln1 = LayerNorm(in_channels=units)
            self.ffn = PositionwiseFFN(units, hidden_size, dropout,
                                       activation=activation)
            self.ln2 = LayerNorm(in_channels=units)

    def forward(self, x, mask=None):
        x = self.ln1(x + self.attention(x, mask))
        x = self.ln2(x + self.ffn(x))
        return x

    def forward_collect(self, x, mask=None):
        """Prefill view: the normal layer forward, plus this layer's
        (B, S, H*D) K/V for the cache."""
        a, k, v = self.attention.forward_collect(x, mask)
        x = self.ln1(x + a)
        x = self.ln2(x + self.ffn(x))
        return x, k, v

    def decode_step(self, x, lengths, k_pool, v_pool, tables, layer):
        """Incremental view: one token (B, H*D) against cached context.
        Residual + post-LN structure is identical to ``forward`` — every op
        is per-row, which is what keeps batched decode bitwise equal to
        serial decode (see serving/generate/)."""
        a, k, v = self.attention.attend_step(x, lengths, k_pool, v_pool,
                                             tables, layer)
        x = self.ln1(x + a)
        x = self.ln2(x + self.ffn(x))
        return x, k, v


class BERTEncoder(HybridBlock):
    def __init__(self, num_layers, units, hidden_size, num_heads, dropout=0.0,
                 activation="gelu_tanh", causal=False, **kwargs):
        super().__init__(**kwargs)
        self._layers = []
        with self.name_scope():
            for i in range(num_layers):
                layer = TransformerEncoderLayer(units, hidden_size, num_heads,
                                                dropout, activation=activation,
                                                causal=causal)
                self.register_child(layer, f"layer{i}")
                self._layers.append(layer)

    def forward(self, x, mask=None):
        for layer in self._layers:
            x = layer(x, mask)
        return x


class BERTModel(HybridBlock):
    """Embeddings + encoder + pooler. Returns (sequence_output, pooled_output)."""

    def __init__(self, num_layers=12, units=768, hidden_size=3072, num_heads=12,
                 vocab_size=30522, max_length=512, type_vocab_size=2,
                 dropout=0.1, activation="gelu_tanh", **kwargs):
        super().__init__(**kwargs)
        self._units = units
        with self.name_scope():
            self.word_embed = Embedding(vocab_size, units)
            self.token_type_embed = Embedding(type_vocab_size, units)
            self.position_embed = Embedding(max_length, units)
            self.embed_ln = LayerNorm(in_channels=units)
            self.embed_drop = Dropout(dropout)
            self.encoder = BERTEncoder(num_layers, units, hidden_size, num_heads,
                                       dropout, activation=activation)
            self.pooler = Dense(units, activation="tanh", flatten=False,
                                in_units=units)

    def forward(self, tokens, token_types=None, valid_mask=None):
        F = _F()
        B, S = tokens.shape[0], tokens.shape[1]
        positions = F.arange(0, S, dtype="int32")
        h = self.word_embed(tokens) + self.position_embed(positions)
        if token_types is not None:
            h = h + self.token_type_embed(token_types)
        h = self.embed_drop(self.embed_ln(h))
        attn_mask = None
        if valid_mask is not None:
            # (B, S) valid-token mask -> (B, 1, 1, S) attention mask
            attn_mask = valid_mask.reshape(B, 1, 1, S)
        seq = self.encoder(h, attn_mask)
        pooled = self.pooler(F.slice_axis(seq, axis=1, begin=0, end=1)
                             .reshape(B, self._units))
        return seq, pooled


class BERTForPretraining(HybridBlock):
    """MLM + NSP heads over BERTModel; output logits.

    forward(tokens, token_types, valid_mask) -> (mlm_logits, nsp_logits).
    The MLM decoder ties to the word embedding (standard BERT)."""

    def __init__(self, backbone: BERTModel, vocab_size=30522, **kwargs):
        super().__init__(**kwargs)
        self._vocab = vocab_size
        with self.name_scope():
            self.backbone = backbone
            self.mlm_transform = Dense(backbone._units, activation=None,
                                       flatten=False, in_units=backbone._units)
            self.mlm_ln = LayerNorm(in_channels=backbone._units)
            self.nsp = Dense(2, flatten=False, in_units=backbone._units)

    def forward(self, tokens, token_types=None, valid_mask=None,
                masked_positions=None):
        """With ``masked_positions`` (B, P) the MLM transform + vocab decoder
        run ONLY at those positions — (B, P, V) logits instead of
        (B, S, V). At the standard ~15% masking rate (P=19 of 128) this
        cuts the vocab-matmul (the largest single matmul in the step)
        ~6.7×; the dense path stays for full-sequence scoring."""
        F = _F()
        seq, pooled = self.backbone(tokens, token_types, valid_mask)
        if masked_positions is not None:
            # gather as a one-hot batched matmul: XLA lowers a plain gather
            # (and its scatter-add backward) to slow non-MXU custom fusions
            # (~27% of the step measured); (B,P,S)@(B,S,U) rides the MXU and
            # its backward is just the transposed matmul
            S = seq.shape[1]
            onehot = F.one_hot(masked_positions, depth=S).astype(seq.dtype)
            seq = F.batch_dot(onehot, seq)                 # (B, P, U)
        h = self.mlm_ln(F.gelu(self.mlm_transform(seq)))
        embed_w = self.backbone.word_embed.weight.data(
            h.context if hasattr(h, "context") else None)
        mlm = F.dot(h.reshape(-1, h.shape[-1]), embed_w.T) \
            .reshape(h.shape[0], h.shape[1], self._vocab)
        return mlm, self.nsp(pooled)


class BERTPretrainingLoss(HybridBlock):
    """Masked-LM + NSP loss. mlm_labels uses -1 for unmasked (ignored) positions
    (the reference's SoftmaxOutput ignore_label convention, nn/softmax-inl.h)."""

    def forward(self, mlm_logits, nsp_logits, mlm_labels, nsp_labels):
        F = _F()
        V = mlm_logits.shape[-1]
        logp = F.log_softmax(mlm_logits, axis=-1)
        labels = mlm_labels.astype("int32")
        safe = F.maximum(labels, F.zeros_like(labels))
        picked = F.pick(logp, safe.astype("float32"), axis=-1)
        valid = (labels >= F.zeros_like(labels)).astype("float32")
        mlm_loss = -(picked * valid).sum() / F.maximum(
            valid.sum(), F.ones_like(valid.sum()))
        nsp_logp = F.log_softmax(nsp_logits, axis=-1)
        nsp_loss = -F.pick(nsp_logp, nsp_labels.astype("float32"), axis=-1).mean()
        return mlm_loss + nsp_loss


class TransformerLM(HybridBlock):
    """Decoder-only causal language model over the BERT encoder stack.

    The generative-serving model: same post-LN transformer layers with the
    bottom-right causal mask baked in (``causal=True`` threads down to
    ``multi_head_attention``), word + position embeddings, and an LM head
    tied to the word embedding (the BERTForPretraining MLM idiom). Three
    entry points share one parameter set:

    - ``forward(tokens)``: full causal pass, (B, S) -> (B, S, V) logits —
      the training/scoring path and the decode oracle's reference.
    - ``prefill_collect(tokens, last=None)``: full causal pass that also
      returns every layer's (B, S, H*D) K/V — compiled per sequence-length
      bucket as the prefill executable. With ``last`` (B,), each prompt's
      last position, the head multiplies that one row: (B, 1, V) logits.
    - ``decode_step(ids, positions, k_pool, v_pool, tables)``: one token per
      row against its cached context, which every layer reads in the paged
      KV pools through the rows' page tables — compiled per batch bucket as
      the decode-step executable. ``positions`` (B,) is both the
      position-embedding index and the cached length (token t has t
      predecessors).

    Both incremental entry points are traced through ``pure_apply(...,
    method=...)`` by serving/generate/engine.py.
    """

    prefill_reads_row = True    # prefill_collect(tokens, last)

    def __init__(self, num_layers=2, units=64, hidden_size=128, num_heads=2,
                 vocab_size=256, max_length=128, dropout=0.0,
                 activation="gelu_tanh", **kwargs):
        super().__init__(**kwargs)
        self.num_layers = num_layers
        self.units = units
        self.num_heads = num_heads
        self.vocab_size = vocab_size
        self.max_length = max_length
        with self.name_scope():
            self.word_embed = Embedding(vocab_size, units)
            self.position_embed = Embedding(max_length, units)
            self.embed_ln = LayerNorm(in_channels=units)
            self.embed_drop = Dropout(dropout)
            self.encoder = BERTEncoder(num_layers, units, hidden_size,
                                       num_heads, dropout,
                                       activation=activation, causal=True)

    def _embed_w(self, h):
        return self.word_embed.weight.data(
            h.context if hasattr(h, "context") else None)

    def forward(self, tokens):
        F = _F()
        S = tokens.shape[1]
        positions = F.arange(0, S, dtype="int32")
        h = self.word_embed(tokens) + self.position_embed(positions)
        h = self.embed_drop(self.embed_ln(h))
        h = self.encoder(h, None)
        embed_w = self._embed_w(h)
        return F.dot(h.reshape(-1, h.shape[-1]), embed_w.T) \
            .reshape(h.shape[0], h.shape[1], self.vocab_size)

    def prefill_collect(self, tokens, last=None):
        """(B, S) tokens -> (logits (B, S, V), k_0, v_0, ..., k_{n-1},
        v_{n-1}) with each k/v (B, S, H*D). ``last`` (B,), if given: the
        position of the row a sequence whose logits are read (a prompt's
        last); that row is taken before the head and the logits are
        (B, 1, V)."""
        F = _F()
        S = tokens.shape[1]
        positions = F.arange(0, S, dtype="int32")
        h = self.word_embed(tokens) + self.position_embed(positions)
        h = self.embed_drop(self.embed_ln(h))
        kvs = []
        for layer in self.encoder._layers:
            h, k, v = layer.forward_collect(h, None)
            kvs.extend((k, v))
        if last is not None:
            h = F.take_along_axis(h, last.reshape(-1, 1, 1), axis=1)
        embed_w = self._embed_w(h)
        logits = F.dot(h.reshape(-1, h.shape[-1]), embed_w.T) \
            .reshape(h.shape[0], h.shape[1], self.vocab_size)
        return (logits,) + tuple(kvs)

    def decode_step(self, ids, positions, k_pool, v_pool, tables):
        """One decode step. ``ids``/``positions`` (B,) int32; ``k_pool``/
        ``v_pool`` the whole paged pools (layers, pages, page_size, H*D) and
        ``tables`` (B, P) int32 each row's pages. Returns (logits (B, V),
        k_new_0, v_new_0, ...) with each new k/v (B, H*D) for the caller to
        write into the pool: no layer reads this step's rows there."""
        F = _F()
        h = self.word_embed(ids) + self.position_embed(positions)
        h = self.embed_drop(self.embed_ln(h))
        kvs = []
        for i, layer in enumerate(self.encoder._layers):
            h, k, v = layer.decode_step(h, positions, k_pool, v_pool, tables,
                                        i)
            kvs.extend((k, v))
        embed_w = self._embed_w(h)
        logits = F.dot(h, embed_w.T)
        return (logits,) + tuple(kvs)


def bert_base(vocab_size=30522, max_length=512, dropout=0.1, **kwargs):
    return BERTModel(num_layers=12, units=768, hidden_size=3072, num_heads=12,
                     vocab_size=vocab_size, max_length=max_length,
                     dropout=dropout, **kwargs)


def bert_large(vocab_size=30522, max_length=512, dropout=0.1, **kwargs):
    return BERTModel(num_layers=24, units=1024, hidden_size=4096, num_heads=16,
                     vocab_size=vocab_size, max_length=max_length,
                     dropout=dropout, **kwargs)


def shard_for_tensor_parallel(model: HybridBlock, tp_axis: str = "tp"):
    """Annotate transformer weights with Megatron-style tensor-parallel specs.

    Dense weights are (out, in): QKV and FFN-in shard the OUT dim (column
    parallel — each chip holds a head/neuron slice); proj and FFN-out shard the
    IN dim (row parallel — XLA inserts the all-reduce after the matmul).
    Embeddings shard the hidden dim. ParallelTrainStep reads the specs.

    Walks the block structure (auto-generated parameter names carry no role
    information), so it works on any model composed of these blocks.
    Returns the number of parameters annotated.
    """
    from jax.sharding import PartitionSpec as P
    count = [0]

    def annotate(p, spec):
        p.shard(spec)
        count[0] += 1

    def visit(block):
        if isinstance(block, SelfAttention):
            annotate(block.qkv.weight, P(tp_axis, None))
            annotate(block.qkv.bias, P(tp_axis))
            annotate(block.proj.weight, P(None, tp_axis))
        elif isinstance(block, PositionwiseFFN):
            annotate(block.ffn1.weight, P(tp_axis, None))
            annotate(block.ffn1.bias, P(tp_axis))
            annotate(block.ffn2.weight, P(None, tp_axis))
        elif isinstance(block, BERTModel):
            annotate(block.word_embed.weight, P(None, tp_axis))

    model.apply(visit)
    return count[0]


def _F():
    from ... import ndarray as nd_mod
    return nd_mod
