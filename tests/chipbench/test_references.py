"""Both plain references against the system at tiny widths, in float32 on the
CPU. The tolerances are float32 rounding through a few layers: a reference or
a system that computed in bfloat16, dropped a term or permuted the heads
would miss them by orders of magnitude."""
import numpy as onp
import pytest

from chipbench.harness import load_cell


def _f32_cell(name, **override):
    cell, config = load_cell(name, rehearse=True)
    return {**cell, "compute_dtype": None, **override}, config


def _system_first_loss(spec, batches):
    import jax
    from mxnet_tpu import parallel
    mesh = parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step = parallel.ParallelTrainStep(
        spec.block, spec.loss, spec.optimizer, mesh,
        compute_dtype=spec.compute_dtype, extra_specs=spec.extra_specs)
    return float(step.step_n(*step.place_batch_n(*batches)).asnumpy()[0])


def _batches(spec, k, samples, seed=3):
    import jax
    return jax.jit(spec.make_batches, static_argnums=(1, 2))(
        jax.random.PRNGKey(seed), k, samples)


def test_bert_pretrain_loss_matches_the_system():
    import mxnet_tpu as mx
    from chipbench.models import bert
    cell, config = _f32_cell("bert_base.pretrain_s128")
    config = {**config, "hidden_dropout_prob": 0.0}   # the reference has none
    spec = bert.build_train(config, cell, 11, mx.cpu(0))
    batches = _batches(spec, 1, 4)
    want = spec.reference_loss(batches)
    got = _system_first_loss(spec, batches)
    assert got == pytest.approx(want, rel=2e-5)


def test_bert_reference_depends_on_every_input():
    """Guards the reference itself: another mask position, label or token
    type changes its loss."""
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from chipbench.models import bert
    cell, config = _f32_cell("bert_base.pretrain_s128")
    spec = bert.build_train(config, cell, 11, mx.cpu(0))
    toks, (mlm, nsp), types_, pos = _batches(spec, 1, 4)
    base = spec.reference_loss((toks, (mlm, nsp), types_, pos))
    for other in ((toks, ((mlm + 1) % 128, nsp), types_, pos),
                  (toks, (mlm, 1 - nsp), types_, pos),
                  (toks, (mlm, nsp), jnp.ones_like(types_), pos),
                  ((toks + 1) % 128, (mlm, nsp), types_, pos)):
        assert abs(spec.reference_loss(other) - base) > 1e-4


def test_lm_logits_match_the_system():
    import mxnet_tpu as mx
    from chipbench.models import transformer_lm
    _, config = load_cell("gpt1.decode_chat", rehearse=True)
    lm = transformer_lm.build_lm(config, 5)
    toks = onp.random.default_rng(0).integers(
        0, config["vocab_size"], (3, 24)).astype("int32")
    lm(mx.nd.array(toks, dtype="int32"))               # shapes
    want = onp.asarray(transformer_lm.reference_logits(lm, config, toks))
    got = lm(mx.nd.array(toks, dtype="int32")).asnumpy()
    assert got.shape == want.shape == (3, 24, config["vocab_size"])
    onp.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    # causal: a later token does not reach an earlier position
    toks2 = toks.copy()
    toks2[:, -1] = (toks2[:, -1] + 1) % config["vocab_size"]
    again = onp.asarray(transformer_lm.reference_logits(lm, config, toks2))
    onp.testing.assert_array_equal(again[:, :-1], want[:, :-1])
    assert not onp.allclose(again[:, -1], want[:, -1])


def test_resnet_loss_matches_the_system():
    import mxnet_tpu as mx
    from chipbench.models import resnet
    cell, config = _f32_cell("resnet50_v1.train_b128")
    spec = resnet.build_train(config, cell, 7, mx.cpu(0))
    batches = _batches(spec, 1, 8)
    want = spec.reference_loss(batches)
    got = _system_first_loss(spec, batches)
    assert got == pytest.approx(want, rel=1e-4)
