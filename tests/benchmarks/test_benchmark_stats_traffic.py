"""The arithmetic and the traffic generator, against hand-worked numbers."""

import pytest

from benchmarks import stats
from benchmarks import traffic_gen as traffic


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 21)), 95, 19.05), ([7], 95, 7.0),
    ([10, 20], 95, 19.5), ([3, 1, 2], 0, 1.0), ([3, 1, 2], 100, 3.0)])
def test_percentile_is_numpys_linear_rule(values, q, want):
    np = pytest.importorskip("numpy")
    assert stats.percentile(values, q) == pytest.approx(want)
    assert stats.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 95) is None


def test_whole_step_rate_counts_only_between_fences():
    # 3 fences: 0 items at t=10, 80 at t=11, 240 at t=13.5: 240 / 3.5
    fences = [(10.0, 0), (11.0, 80), (13.5, 240)]
    assert stats.whole_step_rate(fences) == pytest.approx(240 / 3.5)
    assert stats.whole_step_rate(fences[:1]) is None
    assert stats.interval_step_seconds(fences, 8) == pytest.approx(
        [0.1, 0.125])


def _tiny_batches(config, mix_name, seeds):
    """The builder's one batch for each seed, at the rehearsal sizes."""
    import json
    import os

    import jax
    import numpy as np

    from benchmarks import harness

    bdir = os.path.dirname(os.path.abspath(traffic.__file__))
    with open(os.path.join(bdir, "configs", config + ".json")) as f:
        cfg = json.load(f)
    mix = traffic.load_mix(mix_name)
    cfg.update(cfg["rehearsal"])
    mix.update(mix["rehearsal"], chips=1)
    built = harness.load_by_name(os.path.join(bdir, "builders"),
                                 cfg["builder"]).build_train(
        cfg, mix, jax.devices()[:1], 0, "")
    return [[np.asarray(b) for b in built["make_batch"](
        np.int32(traffic.fold_seed(s, 2)))] for s in seeds]


@pytest.mark.parametrize("config,mix", [
    ("gpt2_small", "train_1chip_b16_s1024"),
    ("alexnet_owt", "train_searched_4chip_b8192")])
def test_inputs_reproduce_from_the_seed_and_differ_between_seeds(config,
                                                                 mix):
    a, again, b = _tiny_batches(config, mix, [2**31 + 11, 2**31 + 11,
                                              2**31 + 12])
    for x, y, z in zip(a, again, b):
        assert x.shape == y.shape == z.shape       # the same work
        assert (x == y).all() and (x != z).any()   # other values


def test_a_mix_is_found_by_its_name_alone(tmp_path):
    (tmp_path / "m.json").write_text('{"name": "other", "kind": "train"}')
    with pytest.raises(ValueError):
        traffic.load_mix("m", str(tmp_path))
    (tmp_path / "m.json").write_text('{"name": "m", "kind": "train"}')
    assert traffic.load_mix("m", str(tmp_path))["kind"] == "train"


@pytest.mark.parametrize("seed", [0, 2**31 - 1, 2**31, 2**31 + 12345])
def test_fold_seed_fits_31_bits_and_separates_streams(seed):
    assert 0 <= traffic.fold_seed(seed) < 2**31
    assert traffic.fold_seed(seed, 0) != traffic.fold_seed(seed, 1)
    assert traffic.fold_seed(seed) != traffic.fold_seed(seed + 1)
