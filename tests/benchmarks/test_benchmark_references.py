"""Each configuration's plain reference against the system, at a tiny
size on the CPU: the loss of the system's own train step and the
gradient it applied, over all leaves and op by op.

Tolerances (the configurations' ``rehearsal.tolerance``): both sides
compute in float32 here, so the loss agrees to 1e-4 relative (it agrees
to 1e-7; the room is for another BLAS); the gradient is recovered from a
float32 weight update, which costs about 1e-7 / lr of a weight's size,
so 2e-3 in relative L2 over all leaves, and 1e-2 in any one op beyond
that rounding (``compare.rounding_floor``).  A system computing in
bfloat16 misses each of these by a factor of ten or more (Inception at
batch 4 on the CPU: 4e-2 over all leaves, 0.18 in ``conv1``)."""

import json
import os

import jax
import numpy as np
import pytest

from benchmarks import compare, harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BDIR = os.path.join(ROOT, "benchmarks")


def _load(config, mix):
    with open(os.path.join(BDIR, "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BDIR, "traffic", mix + ".json")) as f:
        m = json.load(f)
    cfg.update(cfg["rehearsal"])
    m.update(m["rehearsal"])
    return cfg, m


class _Ctx:
    def __init__(self, cfg, mix):
        self.config, self.mix = cfg, mix
        self.devices = jax.devices()[:1]

    def reference(self):
        return harness.load_by_name(os.path.join(BDIR, "reference"),
                                    self.config["name"])


def _train_errors(config, mix_name, batch=None):
    cfg, mix = _load(config, mix_name)
    mix["chips"] = 1
    if batch:
        mix["batch"] = batch
        mix["reference_chunk"] = max(batch // 2, 1)   # two chunks add up
    ctx = _Ctx(cfg, mix)
    built = harness.load_by_name(os.path.join(BDIR, "builders"),
                                 cfg["builder"]).build_train(
        cfg, mix, ctx.devices, 11, "")
    ff = built["model"]

    def fresh():
        params, state = ff.init()
        return params, state, ff.init_opt_state(params)

    problems, notes = compare.train_step(ctx, built, fresh,
                                         ff.make_train_step(),
                                         built["make_batch"](np.int32(3)))
    return problems, notes


TRAIN = [("alexnet_owt", "train_searched_4chip_b8192", 4),
         ("gpt2_small", "train_1chip_b16_s1024", None),
         ("inception_v3", "train_1chip_b256", 2)]


@pytest.fixture(scope="module", params=TRAIN, ids=lambda t: t[0])
def train_case(request):
    return _train_errors(*request.param)


def test_reference_loss_matches_the_train_steps(train_case):
    problems, notes = train_case
    assert notes["loss_rel_err"] <= 1e-4, notes


def test_reference_gradient_matches_the_applied_one(train_case):
    problems, notes = train_case
    assert notes["grad_rel_l2"] <= 2e-3, notes
    assert problems == []


def test_a_wrong_gradient_is_caught():
    """The comparison is not vacuous: a gradient scaled by 1.1 in one
    layer fails the configuration's own tolerance."""
    g = {"a": {"w": np.ones((4, 4), np.float32)},
         "b": {"w": np.ones((4, 4), np.float32)}}
    bad = {"a": {"w": 1.1 * g["a"]["w"]}, "b": g["b"]}
    zero = {k: {"w": np.zeros((4, 4), np.float32)} for k in g}
    total = compare.rel_l2(bad, g)
    per = compare.per_op_errors(bad, g, zero)
    assert per["a"]["err"] == pytest.approx(0.1, rel=1e-5)
    assert per["b"]["err"] == 0.0
    assert total == pytest.approx(0.1 / 2 ** 0.5, rel=1e-5)
    assert total > 2e-3


def test_applied_gradient_inverts_sgd_with_weight_decay():
    p0 = {"w": np.array([1.0, -2.0], np.float32)}
    g = {"w": np.array([0.5, 0.25], np.float32)}
    opt = {"learning_rate": 0.1, "weight_decay": 0.01}
    p1 = {"w": p0["w"] - 0.1 * (g["w"] + 0.01 * p0["w"])}
    got = compare.applied_gradient(p0, p1, opt)
    np.testing.assert_allclose(got["w"], g["w"], rtol=1e-5)


def test_every_op_is_held_to_the_reference(train_case):
    problems, notes = train_case
    assert notes["worst_op_grad_rel_l2"] <= 1e-2, notes
    assert notes["ops"] > len(notes["ops_under_rounding_floor"])


def test_a_doubled_leaf_hides_in_the_norm_of_all_and_not_in_its_own():
    """What PR 24 met on a spatially split convolution: one small op's
    gradient exactly doubled.  Over all leaves it reads 1%, under any
    tolerance bfloat16 needs; in its own op it reads 100%."""
    rng = np.random.RandomState(0)
    g = {"big": {"w": rng.randn(100, 100).astype(np.float32)},
         "small": {"w": 0.01 * rng.randn(10, 10).astype(np.float32)}}
    bad = {"big": g["big"], "small": {"w": 2 * g["small"]["w"]}}
    zero = {k: {"w": np.zeros_like(v["w"])} for k, v in g.items()}
    total = compare.rel_l2(bad, g)
    per = compare.per_op_errors(bad, g, zero)
    assert total < 2e-3
    assert per["small"]["err"] == pytest.approx(1.0, rel=1e-5)
    assert per["big"]["err"] == 0.0 and per["big"]["resolved"]


def test_rounding_floor_is_half_a_spacing_over_the_learning_rate():
    # a gain at 1.0 moved just under it: spacing 2**-23 at 1.0 (the
    # larger of the two), so 2**-24 / lr of gradient cannot be seen
    p0 = {"g": np.array([1.0, 0.125], np.float32)}
    p1 = {"g": np.array([1.0 - 2.0 ** -24, 0.125], np.float32)}
    floor = compare.rounding_floor(p0, p1, {"learning_rate": 1e-3})
    np.testing.assert_allclose(floor["g"], [2.0 ** -24 / 1e-3,
                                            2.0 ** -27 / 1e-3], rtol=1e-6)


def test_an_error_inside_the_rounding_is_not_judged_and_one_beyond_is():
    ref = {"ln": {"g": np.full(4, 2e-5, np.float32)},
           "fc": {"w": np.full(4, 1.0, np.float32)}}
    floor = {"ln": {"g": np.full(4, 6e-5, np.float32)},
             "fc": {"w": np.full(4, 1e-6, np.float32)}}
    got = {"ln": {"g": np.zeros(4, np.float32)},        # rounded away
           "fc": {"w": np.full(4, 1.5, np.float32)}}
    per = compare.per_op_errors(got, ref, floor)
    assert not per["ln"]["resolved"] and per["ln"]["raw"] == \
        pytest.approx(1.0)
    assert per["ln"]["err"] == 0.0
    assert per["fc"]["resolved"]
    assert per["fc"]["err"] == pytest.approx(0.5, rel=1e-4)


@pytest.mark.parametrize("config,want_m", [("inception_v3", 23.8),
                                           ("alexnet_owt", 61.1)])
def test_cnn_shape_tables_hold_the_published_parameter_counts(config, want_m):
    mod = harness.load_by_name(os.path.join(BDIR, "flops"), config)
    import math

    n = sum(math.prod(k) + k[-1] for _, k in mod.LAYERS)
    assert n / 1e6 == pytest.approx(want_m, abs=0.06)


@pytest.mark.parametrize("config,mix,want_g", [
    # 5.8 GMAC forward an image (Szegedy et al.), x 2 x 3, less conv1's dgrad
    ("inception_v3", "train_1chip_b256", 34.86),
    # 0.71 GMAC forward (Krizhevsky 2014's 5 conv + 3 fc), same rule
    ("alexnet_owt", "train_searched_4chip_b8192", 4.14),
    # 6 x 85M block parameters + 6 x 38.6M head + causal attention
    ("gpt2_small", "train_1chip_b16_s1024", 0.798)])
def test_model_flops_per_item(config, mix, want_g):
    with open(os.path.join(BDIR, "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BDIR, "traffic", mix + ".json")) as f:
        m = json.load(f)
    mod = harness.load_by_name(os.path.join(BDIR, "flops"), config)
    assert mod.train_flops_per_item(cfg, m) / 1e9 == pytest.approx(
        want_g, rel=2e-3)


def test_flash_kernel_work_from_shapes():
    with open(os.path.join(BDIR, "configs", "gpt2_small.json")) as f:
        cfg = json.load(f)
    mix = {"batch": 16, "seq_length": 1024}
    mod = harness.load_by_name(os.path.join(BDIR, "flops"), "gpt2_small")
    work = mod.kernel_work(cfg, mix)["ff_flash_"]
    # 12 layers x 16 x 12 heads x 6 products x 2 x 1024^2 x 64 / 2 (causal)
    assert work["flops"] == pytest.approx(12 * 16 * 12 * 6 * 2 * 1024 ** 2
                                          * 64 / 2)
    # 12 tensors of 16 x 12 x 1024 x 64 bf16 a layer
    assert work["bytes"] == pytest.approx(12 * 12 * 16 * 12 * 1024 * 64 * 2)
