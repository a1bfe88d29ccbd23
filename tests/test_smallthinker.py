"""What SmallThinker-21BA3B-Instruct forced (PR 40): ReGLU held experts
through ``ff_gmm``, a router read from the block's input before attention,
NoPE full and rotary windowed attention in one model, and the model class
``apps/lm.py`` trains from a file with a ``model_name`` and no
``model_type``: each against plain ``jax.numpy``, the whole model against
``benchmarks/reference/smallthinker_21b_a3b.py``, and the train steps of
the Moonlight, Laguna, LFM2 and Granite cells held to the parent's."""

import contextlib
import hashlib
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.machine import MachineModel
from flexflow_tpu.ops.base import Tensor
from flexflow_tpu.strategy import ParallelConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                      "smallthinker_21b_a3b.json")


def _pc(rank):
    return ParallelConfig((1,) * rank, (0,))


def _rand(seed, *shape, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


def _reference():
    from benchmarks.reference import smallthinker_21b_a3b

    return smallthinker_21b_a3b


def _config(**over):
    with open(CONFIG) as f:
        config = json.load(f)
    config.update(over)
    return config


def _tiny_config(**over):
    config = _config()
    config.update(config["rehearsal"])
    config.update(over)
    return config


def _counted(name):
    from flexflow_tpu import obs

    return obs.snapshot()["counters"].get(name, 0)


# ---------------------------------------------------------------------------
# ReGLU through the grouped products


@pytest.fixture
def small_tiles(monkeypatch):
    """Row tiles of 32 and 16 walked in pieces of 8, so that a buffer of
    48 rows is the kernels' and interpret mode walks boundaries as the
    chip does at 512 (``tests/test_grouped_mm.py``'s cut)."""
    from flexflow_tpu.ops.pallas import grouped_mm as gm

    monkeypatch.setattr(gm, "_ROW_TILES", (32, 16))
    monkeypatch.setattr(gm, "_SUB_ROWS", 8)
    gm._make_gated_ffn.cache_clear()
    yield gm
    gm._make_gated_ffn.cache_clear()


def test_reglu_grouped_products_against_plain_jnp(small_tiles):
    """``relu(rows W_gate[g]) * (rows W_up[g]) W_down[g]`` group by group,
    forward and the VJP of rows and the three stacks, against the same
    written out in ``jax.numpy``; rows of no group read 0."""
    gm = small_tiles
    sizes = np.array([10, 0, 17, 5])
    rng = np.random.RandomState(2)
    rows = jnp.asarray(rng.randn(48, 128), jnp.float32)
    ws = [jnp.asarray(rng.randn(*s) * 0.1, jnp.float32)
          for s in ((4, 128, 128), (4, 128, 128), (4, 128, 128))]
    d_y = jnp.asarray(rng.randn(48, 128), jnp.float32)
    starts = np.concatenate([[0], np.cumsum(sizes)])

    def plain(rows, w_gate, w_up, w_down):
        out = jnp.zeros_like(rows)
        for g, (lo, hi) in enumerate(zip(starts, starts[1:])):
            r = rows[lo:hi]
            h = jax.nn.relu(r @ w_gate[g]) * (r @ w_up[g])
            out = out.at[lo:hi].set(h @ w_down[g])
        return out

    with jax.default_matmul_precision("highest"):
        want, pull_want = jax.vjp(plain, rows, *ws)
        want_grads = pull_want(d_y)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    y, tiles = gm.gated_ffn(rows, group_sizes, *ws, act="relu")
    assert tiles == (16, 128, 128)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    assert not np.asarray(y[32:]).any()
    _, pull = jax.vjp(lambda r, *w: gm.gated_ffn(
        r, group_sizes, *w, act="relu")[0], rows, *ws)
    for got, ref in zip(pull(d_y), want_grads):
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
    # a ReLU gate is not a SiLU one: the default gives other numbers
    silu, _ = gm.gated_ffn(rows, group_sizes, *ws)
    assert float(jnp.max(jnp.abs(silu - y))) > 1e-3
    # the ragged_dot path off the kernels computes the same
    from flexflow_tpu.ops.expert_share import grouped_gated_ffn

    with jax.default_matmul_precision("highest"):
        ragged = grouped_gated_ffn(rows, group_sizes, *ws, act="relu")
    np.testing.assert_allclose(ragged[:32], want[:32], rtol=1e-4, atol=1e-5)


def test_held_experts_take_the_activation_they_are_given():
    from flexflow_tpu.ops.expert_share import HeldExperts

    def experts(**extra):
        return HeldExperts("e", _pc(2), Tensor((2, 12, 16)),
                           Tensor((2, 12, 8)), 24, (2, 6), 2, **extra)

    assert experts().act == "silu"
    relu = experts(act="relu")
    assert relu.cost_signature()[-1] == "relu"
    with pytest.raises(ValueError, match="expert activation 'gelu'"):
        experts(act="gelu")
    before = _counted("moe.expert_act.relu")
    params = relu.init_params(jax.random.PRNGKey(1))
    gates = jnp.zeros((2, 12, 8)).at[..., 3].set(1.0)
    y, _ = relu.forward(params, relu.init_state(), [_rand(1, 2, 12, 16),
                                                    gates], True)
    assert _counted("moe.expert_act.relu") == before + 1
    x = _rand(1, 2, 12, 16).reshape(24, 16)
    w = {k: v[1] for k, v in params.items()}     # expert 3 is row 1 here
    want = (jax.nn.relu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]
    np.testing.assert_allclose(y.reshape(24, 16), want, rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("reads", ["ffn", "block"])
def test_held_experts_count_what_their_router_read(reads):
    """``ffn`` where the router read the experts' own input (Moonlight's,
    Laguna's, LFM2's wiring), ``block`` where it read another tensor
    (SmallThinker's: the block's normed input, before attention)."""
    from flexflow_tpu.ops.expert_share import HeldExperts, TopKRouter

    m = Tensor((2, 12, 16))
    router = TopKRouter("r", _pc(2), m if reads == "ffn"
                        else Tensor((2, 12, 16)), 8, 2, 1.0,
                        score="softmax")
    op = HeldExperts("e", _pc(2), m, router.output, 24, (2, 6), 2)
    before = {k: _counted(f"moe.router_input.{k}") for k in
              ("ffn", "block")}
    gates = jnp.zeros((2, 12, 8)).at[..., 3].set(1.0)
    op.forward(op.init_params(jax.random.PRNGKey(1)), op.init_state(),
               [_rand(1, 2, 12, 16), gates], True)
    assert {k: _counted(f"moe.router_input.{k}") - v
            for k, v in before.items()} == {
        k: int(k == reads) for k in before}


# ---------------------------------------------------------------------------
# the model class


@pytest.fixture(scope="module")
def tiny_model():
    from flexflow_tpu.models.smallthinker import (SmallThinkerConfig,
                                                  SmallThinkerLM)

    return SmallThinkerLM(SmallThinkerConfig.from_config(
        _tiny_config(), batch_size=2, seq_length=20),
        MachineModel(jax.devices()[:1]))


def test_model_class_builds_the_named_operators(tiny_model):
    names = [op.name for op in tiny_model.layers]
    block = ["norm1", "moe_router", "attn_{}", "res1", "norm2",
             "moe_experts", "res2"]
    assert names == ["embed"] + [
        f"blk{i}_{n.format(kind)}" for i, kind in
        enumerate(("full", "window", "window")) for n in block] + [
        "final_norm", "lm_head", "softmax"]
    by_name = {op.name: op for op in tiny_model.layers}
    full, win = by_name["blk0_attn_full"], by_name["blk1_attn_window"]
    assert (full.num_heads, full.num_kv_heads, full.head_dim) == (4, 2, 16)
    assert (full.window, full.rope) == (None, None)
    assert win.window == 8 and not win.gate and win.qk_norm is None
    assert win.rope == {"rope_type": "default", "rope_theta": 1500000,
                        "dim": 16}
    assert full.scale == win.scale == 0.25
    router = by_name["blk1_moe_router"]
    assert (router.score, router.n_router, router.top_k, router.scale) \
        == ("softmax", 16, 3, 1.0)
    # the router and attention read one tensor: the block's normed input
    norm1 = by_name["blk1_norm1"].output
    assert router.inputs[0] is norm1 and win.inputs[0] is norm1
    experts = by_name["blk1_moe_experts"]
    assert experts.inputs == [by_name["blk1_norm2"].output, router.output]
    assert experts.experts_held == (4, 8) and experts.act == "relu"
    assert experts.d_ff == 32
    assert len(tiny_model.recompute_blocks) == 3
    params, state = tiny_model.init(1)
    assert "lm_head" in params and "blk1_moe_router" not in state
    # the published model: one NoPE full layer to three rotary windowed
    from flexflow_tpu.models.smallthinker import SmallThinkerConfig

    t = SmallThinkerConfig.from_config(_config())
    assert t.sliding_window_layout == t.rope_layout == (0, 1, 1, 1) * 13
    assert (t.num_layers, t.head_dim, t.sliding_window_size,
            t.num_attention_heads // t.num_key_value_heads) == (4, 128,
                                                                4096, 7)


def test_the_routers_gates_do_not_see_the_attention(tiny_model):
    """Gates read before attention: moving every attention weight of a
    block leaves its router's gates as they were and moves what the
    experts read; moving ``norm1``'s gain moves the gates.  A router read
    after attention fails the first."""
    ff = tiny_model
    params, state = ff.init(2)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 20), 0, 96)
    router = next(op for op in ff.layers if op.name == "blk1_moe_router")
    norm2 = next(op for op in ff.layers if op.name == "blk1_norm2")

    def read(params):
        values, _ = ff.apply(params, state, {ff.tokens.tid: toks,
                                             ff.labels.tid: toks}, False)
        return values[router.output.tid], values[norm2.output.tid]

    gates, normed = read(params)
    assert int(jnp.sum(gates > 0)) == 2 * 20 * 3
    moved = dict(params, blk1_attn_window=jax.tree.map(
        lambda a: 3.0 * a + 0.1, params["blk1_attn_window"]))
    gates2, normed2 = read(moved)
    np.testing.assert_array_equal(gates2, gates)
    assert float(jnp.max(jnp.abs(normed2 - normed))) > 1e-2
    gained = dict(params, blk1_norm1={"scale": _rand(4, 64)})
    assert float(jnp.max(jnp.abs(read(gained)[0] - gates))) > 1e-3
    # and the router's gradient returns through norm1, the experts'
    # through norm2
    grads = jax.grad(lambda p: ff.loss_fn(p, state, toks, toks)[0])(params)
    for name in ("blk1_moe_router", "blk1_norm1", "blk1_norm2"):
        assert float(jnp.max(jnp.abs(jax.tree.leaves(grads[name])[0]))) > 0


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of 4 of 16 ReGLU experts under a softmax top 3: the
    router and attention computed once, as every chip computes them alike,
    and the four held parts added give the uncut reference block."""
    from flexflow_tpu.ops.attention import GroupedQueryAttention
    from flexflow_tpu.ops.expert_share import HeldExperts, TopKRouter
    from flexflow_tpu.ops.seq_gated import RMSNormSeq

    ref = _reference()
    c = _tiny_config(experts_held=[0, 16], moe_num_primary_experts=16)
    d, f, tokens, layer = 64, 32, (2, 24), 1
    x = _rand(14, *tokens, d)
    t = Tensor(tokens + (d,))
    norm1, norm2 = (RMSNormSeq(n, _pc(2), t, 1e-6) for n in ("n1", "n2"))
    router = TopKRouter("r", _pc(2), t, 16, 3, 1.0, score="softmax")
    attn = GroupedQueryAttention(
        "a", _pc(3), t, 4, 2, 16, 0.25,
        rope={"rope_type": "default", "rope_theta": 1.5e6, "dim": 16},
        window=8)

    def share(lo, held=4):
        return HeldExperts("e", _pc(2), t, Tensor(tokens + (16,)), f,
                           (lo, lo + held), 3, act="relu")

    p = {"blk1_norm1": {"scale": 1 + 0.2 * _rand(1, d)},
         "blk1_norm2": {"scale": 1 + 0.2 * _rand(2, d)},
         "blk1_moe_router": router.init_params(jax.random.PRNGKey(3)),
         "blk1_attn_window": attn.init_params(jax.random.PRNGKey(4)),
         "blk1_moe_experts": share(0, 16).init_params(
             jax.random.PRNGKey(5))}
    n, _ = norm1.forward(p["blk1_norm1"], {}, [x], True)
    gates, _ = router.forward(p["blk1_moe_router"], {}, [n], True)
    h = x + attn.forward(p["blk1_attn_window"], {}, [n], True)[0]
    m, _ = norm2.forward(p["blk1_norm2"], {}, [h], True)
    total, pairs = h, 0
    for lo in range(0, 16, 4):
        op = share(lo)
        ps = {k: v[lo:lo + 4] for k, v in p["blk1_moe_experts"].items()}
        y, st = op.forward(ps, op.init_state(), [m, gates], True)
        assert float(st["dropped"]) == 0
        pairs += int(jnp.sum(st["counts"][lo:lo + 4]))
        total = total + y
    assert pairs == 3 * 48, "every selection falls in exactly one share"
    with jax.default_matmul_precision("highest"):
        want = ref._block(p, layer, x, c)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_loss_and_every_operator_gradient_against_the_reference(
        path, tiny_model, pallas_kernels, monkeypatch):
    """Seeded weights; ``kernels``: a model of whole lanes with the kernel
    gate open, so that the head runs in the fused projection+CE kernel,
    attention in the flash kernels (windowed and full) behind ``ff_rope``
    on the windowed layers, and the ReGLU experts in ``ff_gmm``, inside
    recomputed blocks."""
    from benchmarks import harness
    from flexflow_tpu.models.smallthinker import (SmallThinkerConfig,
                                                  SmallThinkerLM)

    ff, cfg, b, s = tiny_model, _tiny_config(), 2, 20
    if path == "kernels":
        fa = importlib.import_module(
            "flexflow_tpu.ops.pallas.flash_attention")
        monkeypatch.setattr(fa, "_WINDOW_BLOCKS", (64, 32))
        monkeypatch.setattr(fa, "_WINDOW_FWD_PIECE", 16)
        monkeypatch.setattr(fa, "_WINDOW_BWD_PIECE", 32)
        cfg, b, s = _tiny_config(
            hidden_size=128, head_dim=128, num_attention_heads=2,
            num_key_value_heads=1, sliding_window_size=64,
            moe_ffn_hidden_size=128, vocab_size=256,
            max_position_embeddings=512), 4, 512
        ff = SmallThinkerLM(SmallThinkerConfig.from_config(
            cfg, batch_size=b, seq_length=s),
            MachineModel(jax.devices()[:1]))
    params, state = ff.init(4)
    # every gain away from its initial value
    params = jax.tree.map(
        lambda a: a + 0.1 * _rand(a.size % 97, *a.shape), params)
    toks = jax.random.randint(jax.random.PRNGKey(5), (b, s), 0,
                              cfg["vocab_size"])
    names = ("kernels.flash.pack1.fused.w64", "kernels.flash.pack1.fused",
             "kernels.rope.pallas.2x128r128", "attn.rope.none",
             "kernels.gmm.ff_gmm.512x128x128")
    before = {k: _counted(k) for k in names}
    with pallas_kernels() if path == "kernels" else \
            contextlib.nullcontext():
        if path == "kernels":
            assert ff._lm_head_fusion()
        (loss, _), grads = jax.value_and_grad(
            lambda p: ff.loss_fn(p, state, toks, toks), has_aux=True)(params)
    if path == "kernels":
        # two windowed layers, one full without rotary, three expert
        # layers through ff_gmm
        assert {k: _counted(k) - v for k, v in before.items()} == dict(
            zip(names, (2, 1, 2, 1, 3)))
    plain = harness.op_params(ff, params)
    with jax.default_matmul_precision("highest"):
        total, want, n = _reference().sum_loss_and_grads(plain, (toks, toks),
                                                         cfg)
    assert n == b * (s - 1)
    np.testing.assert_allclose(loss, total / n, rtol=1e-5)
    assert set(want) == set(grads)
    for op, leaves in want.items():
        for leaf, g in leaves.items():
            scale = float(jnp.max(jnp.abs(g))) / n
            np.testing.assert_allclose(
                grads[op][leaf], g / n, rtol=2e-3, atol=2e-4 * scale + 1e-9,
                err_msg=f"{op}.{leaf}")


def test_recomputed_step_trains_and_counts_the_mechanisms(tiny_model):
    model = tiny_model
    toks = jax.random.randint(jax.random.PRNGKey(7), (2, 20), 0, 96)
    step, (p, st), losses = model.make_train_step(), model.init(3), []
    for _ in range(8):
        p, st, _, loss = step(p, st, None, toks, toks)
        losses.append(float(loss))
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert float(st["blk1_moe_experts"]["dropped"]) == 0
    assert _counted("moe.experts_held") == 4
    assert _counted("attn.window") == 8
    for name in ("attn.kv_groups.2", "attn.rope.none",
                 "moe.router_input.block", "moe.expert_act.relu",
                 "kernels.rope.xla.4x16r16", "runtime.recomputed_blocks"):
        assert _counted(name) >= 1, name


def test_parameters_are_the_count_the_issue_reckons():
    """656.5 M parameters at the cell's size and 21.5 B whole, from the
    operators' own ``param_bytes`` (no array is made)."""
    from flexflow_tpu.models.smallthinker import (SmallThinkerConfig,
                                                  SmallThinkerLM)

    def count(**over):
        ff = SmallThinkerLM(SmallThinkerConfig.from_config(
            _config(**over), batch_size=1, seq_length=16384),
            MachineModel(jax.devices()[:1]))
        return ff, {op.name: op.param_bytes() // 4 for op in ff.layers}

    ff, by_op = count()
    attn = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert by_op["blk0_attn_full"] == by_op["blk1_attn_window"] \
        == attn == 20_971_520
    assert by_op["blk0_moe_router"] == 2560 * 64 == 163_840
    assert by_op["blk3_moe_experts"] == 16 * 3 * 2560 * 768 == 94_371_840
    assert by_op["embed"] == by_op["lm_head"] == 37984 * 2560 == 97_239_040
    layer = attn + 163_840 + 94_371_840 + 2 * 2560
    assert layer == 115_512_320
    assert sum(by_op.values()) == 4 * layer + 2 * 97_239_040 + 2560 \
        == 656_529_920
    experts = next(op for op in ff.layers if op.name == "blk0_moe_experts")
    assert experts.rows_capacity == 49152
    # the whole model: 52 layers, 64 experts held, 151 936 rows
    _, whole = count(num_layers=52, moe_num_primary_experts=64,
                     experts_held=[0, 64], vocab_size=151936)
    assert sum(whole.values()) == 52 * (attn + 163_840 + 64 * 3 * 2560 * 768
                                        + 2 * 2560) \
        + 2 * 151936 * 2560 + 2560 == 21_506_562_560


@pytest.mark.parametrize("key,value", [
    ("moe_primary_router_apply_softmax", False), ("norm_topk_prob", False),
    ("tie_word_embeddings", True), ("rope_scaling", {"type": "yarn"}),
    ("experts_held", [0, 32]), ("sliding_window_layout", [0, 2, 1, 1]),
    ("rope_layout", [0, 1])])
def test_from_config_refuses_what_the_class_does_not_build(key, value):
    from flexflow_tpu.models.smallthinker import SmallThinkerConfig

    SmallThinkerConfig.from_config(_config())           # the file passes
    with pytest.raises(ValueError, match=key.split("_")[0]):
        SmallThinkerConfig.from_config(_config(**{key: value}))
    with pytest.raises(ValueError, match="plain SGD"):
        SmallThinkerConfig.from_config(_config(optimizer={"kind": "adamw"}))


def test_apps_lm_trains_the_model_from_its_configuration_file():
    from flexflow_tpu.apps import lm

    assert lm.MODEL_NAMES["smallthinker_21b_instruct"]
    assert "model_type" not in _config()
    lines = []
    out = lm.main(["--model-config", CONFIG, "--preset", "rehearsal",
                   "-b", "2", "-s", "32", "-i", "12", "--seed", "5"],
                  log=lines.append)
    assert any("by model_name smallthinker_21b_instruct" in l
               and "2 of window 8, 1 full, 1 without rotary" in l
               and "[4, 8) of 16 held" in l for l in lines[:2])
    losses = out["loss"]
    # two batches in turn: the loss starts near ln(vocabulary) and falls
    assert len(losses) == 12 and np.all(np.isfinite(losses))
    assert abs(losses[0] - np.log(96)) < 0.5 and losses[-1] < losses[0]
    assert _counted("moe.dropped_pairs") == 0
    assert _counted("moe.load_max_over_mean") >= 1.0
    with pytest.raises(SystemExit, match="model_name 'other'"):
        lm.main(["--model-config", _write_other(), "-i", "1"],
                log=lines.append)


def _write_other():
    import tempfile

    path = os.path.join(tempfile.mkdtemp(), "other.json")
    with open(path, "w") as f:
        json.dump({"model_name": "other"}, f)
    return path


# ---------------------------------------------------------------------------
# what the other token cells run, held to the parent commit (PR 39)

#: sha256 (first 16) of each cell's whole train step as the parent commit
#: traced it at the cell's shape, the kernel gate open, addresses of
#: Python objects left out
_PARENTS = {"moonlight_16b_a3b": "7fed2ab9cbf18ea7",
            "laguna_s_2_1": "d870f8c241d84db4",
            "lfm2_8b_a1b": "34463073238cd514",
            "granite_4_0_h_micro": "f13cab76c5e4f896"}


@pytest.mark.parametrize("name", sorted(_PARENTS))
def test_the_other_cells_train_steps_trace_to_the_parents(
        name, pallas_kernels, monkeypatch):
    from benchmarks import harness

    for kernel in ("flash_attention", "grouped_mm", "fused_ce", "rope",
                   "ssd_scan"):
        mod = importlib.import_module(f"flexflow_tpu.ops.pallas.{kernel}")
        monkeypatch.setattr(mod, "_should_interpret", lambda: False)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    builder = harness.load_by_name(os.path.join(ROOT, "benchmarks",
                                                "builders"),
                                   config["builder"])
    with pallas_kernels():
        ff = builder.build_train(config, {"batch": 2, "seq_length": 8192},
                                 jax.devices()[:1], 1)["model"]
        params, state, opt = ff.abstract_train_state()
        toks = jax.ShapeDtypeStruct((2, 8192), jnp.int32)
        text = str(jax.make_jaxpr(ff.make_train_step())(
            params, state, opt, toks, toks))
    assert "ff_gmm" in text or name == "granite_4_0_h_micro"
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == _PARENTS[name]
