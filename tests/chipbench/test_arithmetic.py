"""The yardstick's arithmetic: percentiles and spreads, and the FLOPs each
configuration requires against sums made by hand here."""
import statistics

import pytest

from chipbench import harness, stats
from chipbench.models import bert, resnet


@pytest.mark.parametrize("values, q, want", [
    ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 101)), 95, 95.05), ([7], 95, 7.0), ([3, 1, 2], 100, 3.0),
    ([], 95, None)])
def test_percentile_interpolates_between_ranks(values, q, want):
    got = stats.percentile(values, q)
    assert got == want if want is None else got == pytest.approx(want)


def test_percentile_matches_numpy():
    onp = pytest.importorskip("numpy")
    xs = list(onp.random.default_rng(0).lognormal(3, 1, 257))
    for q in (5, 50, 95, 99):
        assert stats.percentile(xs, q) == pytest.approx(onp.percentile(xs, q))


def test_quartile_spread_is_the_instructions_formula():
    xs = [100.0, 101.0, 99.0, 100.5, 102.0, 98.5]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.quartile_spread(xs) == pytest.approx(
        (q3 - q1) / statistics.median(xs))
    assert stats.quartile_spread([5.0]) is None


def test_seed32_takes_seeds_past_31_bits_and_keeps_them_apart():
    seeds = [0, 1, 2**31 - 1, 2**31 + 129, 2**31 + 130, 2**32 + 5]
    got = [harness.seed32(s) for s in seeds]
    assert all(0 <= g < 2**31 for g in got) and len(set(got)) == len(seeds)
    assert harness.seed32(7, 1) != harness.seed32(7, 2)
    assert harness.seed32(7) == harness.seed32(7)


def test_bert_base_s128_flops_against_the_hand_sum():
    cell, config = harness.load_cell("bert_base.pretrain_s128")
    per_token_layer = (2 * 768 * 2304        # q, k, v
                       + 2 * 2 * 128 * 768   # scores, weighted values
                       + 2 * 768 * 768       # projection
                       + 2 * 2 * 768 * 3072)  # feed-forward
    assert per_token_layer == pytest.approx(14.55e6, rel=1e-3)
    head = 19 * (2 * 768 * 768 + 2 * 768 * 30522)
    assert head == pytest.approx(0.913e9, rel=1e-3)
    hand = 3 * (12 * per_token_layer * 128 + head)
    got = bert.flops_per_sample(config, cell)
    assert got == pytest.approx(69.8e9, rel=2e-3)
    assert got == pytest.approx(hand, rel=1e-3)    # + pooler and NSP
    assert bert.masked_positions(128, 0.15) == 19


def test_resnet50_flops_against_the_hand_sum():
    """He et al. Table 1, 50-layer column, by hand: multiply-adds of the stem,
    of each stage's first block (with its projection) and of its other
    blocks, and of the classifier."""
    cell, config = harness.load_cell("resnet50_v1.train_b128")
    stem = 112 * 112 * 7 * 7 * 3 * 64

    def block(hw, c_in, width, project, hw_in=None):
        mid = width // 4
        macs = hw * hw * (c_in * mid + 9 * mid * mid + mid * width)
        return macs + (hw * hw * c_in * width if project else 0)

    stages = (block(56, 64, 256, True) + 2 * block(56, 256, 256, False)
              + block(28, 256, 512, True) + 3 * block(28, 512, 512, False)
              + block(14, 512, 1024, True) + 5 * block(14, 1024, 1024, False)
              + block(7, 1024, 2048, True) + 2 * block(7, 2048, 2048, False))
    hand = stem + stages + 2048 * 1000
    assert 3.8e9 <= hand <= 4.1e9                 # the paper's 3.8 GFLOPs
    assert resnet.flops_per_sample(config, cell) == pytest.approx(6.0 * hand)
    assert len(resnet.conv_shapes(config)) == 1 + 16 * 3 + 4 + 1


def test_prove_summarises_a_set_by_metric():
    from chipbench import prove
    runs = [{"result": {"metrics": {"a": {"value": v, "unit": "x"},
                                    "setup_s": {"value": 40.0 + i, "unit": "s"}}}}
            for i, v in enumerate([100.0, 101.0, 99.0, 100.5])]
    runs.append({"result": None})                  # a run that printed nothing
    got = prove.summarise(runs)
    assert got["a"]["values"] == [100.0, 101.0, 99.0, 100.5]
    assert got["a"]["median"] == 100.25
    assert got["a"]["spread"] == pytest.approx(
        stats.quartile_spread([100.0, 101.0, 99.0, 100.5]))
    assert got["setup_s"]["median"] == 41.5
