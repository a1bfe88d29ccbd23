"""What LFM2-8B-A1B forced (PR 38): the gated short convolution, RMSNorms
on the heads of q and k inside ``GroupedQueryAttention``, the sigmoid
router's published denominator, and the model class ``apps/lm.py`` trains
from an ``lfm2_moe`` configuration: each against plain ``jax.numpy`` or a
loop over positions, the whole model against
``benchmarks/reference/lfm2_8b_a1b.py``, and what Granite's, Laguna's and
Moonlight's cells run held to what it was."""

import contextlib
import hashlib
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lm_check
from flexflow_tpu.machine import MachineModel
from flexflow_tpu.ops.base import Tensor
from flexflow_tpu.strategy import ParallelConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmarks", "configs", "lfm2_8b_a1b.json")


def _pc(rank):
    return ParallelConfig((1,) * rank, (0,))


def _rand(seed, *shape, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


def _reference():
    from benchmarks.reference import lfm2_8b_a1b

    return lfm2_8b_a1b


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
# the gated short convolution


@pytest.mark.parametrize("taps,s", [(3, 11), (5, 11), (3, 2), (1, 4)])
def test_short_conv_against_a_loop_over_positions(taps, s):
    """One position, channel and tap after another in float64; a
    sequence shorter than the taps reads zeros before its start."""
    from flexflow_tpu.ops.short_conv import GatedShortConv

    b, d = 2, 8
    op = GatedShortConv("conv", _pc(2), Tensor((b, s, d)), taps)
    params = op.init_params(jax.random.PRNGKey(taps))
    assert {k: v.shape for k, v in params.items()} == {
        "w_in": (d, 3 * d), "conv_w": (d, taps), "w_out": (d, d)}
    assert np.all(np.abs(params["conv_w"]) <= taps ** -0.5)
    x = _rand(s, b, s, d)
    before = _counted(f"kernels.short_conv.xla.{d}x{taps}")
    got, state = op.forward(params, {}, [x], True)
    assert state == {} and got.shape == (b, s, d)
    assert _counted(f"kernels.short_conv.xla.{d}x{taps}") == before + 1
    assert _counted("conv.taps") == taps
    w_in, w, w_out = (np.asarray(params[k], np.float64)
                      for k in ("w_in", "conv_w", "w_out"))
    proj = np.asarray(x, np.float64) @ w_in
    bb, cc, xx = proj[..., :d], proj[..., d:2 * d], proj[..., 2 * d:]
    u = bb * xx
    v = np.zeros_like(u)
    for i in range(b):
        for t in range(s):
            for c in range(d):
                for j in range(taps):
                    at = t - (taps - 1) + j
                    if at >= 0:
                        v[i, t, c] += w[c, j] * u[i, at, c]
    np.testing.assert_allclose(got, (cc * v) @ w_out, rtol=2e-5, atol=2e-6)
    # and it is the reference's operator, a sequence at a time
    with jax.default_matmul_precision("highest"):
        for i in range(b):
            np.testing.assert_allclose(
                got[i], _reference().short_conv(params, x[i]), rtol=2e-5,
                atol=2e-6)
    # causal: a later position moves no earlier output
    if s > 2:
        later = x.at[:, -1].add(1.0)
        np.testing.assert_array_equal(
            op.forward(params, {}, [later], True)[0][:, :-1], got[:, :-1])


def test_short_conv_counts_what_it_is_and_runs_on_one_grid_only():
    from flexflow_tpu.ops.short_conv import GatedShortConv

    op = GatedShortConv("conv", _pc(2), Tensor((2, 16, 32)), 3)
    assert op.param_bytes() == 4 * (4 * 32 * 32 + 32 * 3)
    assert op.flops_per_sample() == 16 * (8.0 * 32 * 32 + 8.0 * 32)
    assert op.cost_signature() == (3,)
    split = GatedShortConv("conv", ParallelConfig((2, 1), (0, 1)),
                           Tensor((2, 16, 32)), 3)
    with pytest.raises(ValueError, match=r"grid \(1, 1\) only"):
        split.validate_partitioning()
    with pytest.raises(ValueError, match="taps"):
        GatedShortConv("conv", _pc(2), Tensor((2, 16, 32)), 0)


def test_causal_conv_with_a_bias_is_what_the_state_space_mixer_had():
    """``SSMIn``'s call (four taps, a bias) against the sum written out,
    and the new call without one: the same taps, no bias term."""
    from flexflow_tpu.ops.ssm import causal_conv1d

    x, w, b = _rand(1, 2, 9, 6), _rand(2, 6, 4), _rand(3, 6)
    xp = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
    want = b + sum(w[:, j] * xp[:, j:j + 9] for j in range(4))
    np.testing.assert_allclose(causal_conv1d(x, w, b), want, rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(causal_conv1d(x, w, None), want - b,
                               rtol=1e-5, atol=1e-6)
    text = str(jax.make_jaxpr(causal_conv1d)(x, w, b))
    assert text.count(" mul ") == 4 and text.count(" add ") == 4


# ---------------------------------------------------------------------------
# norms on the heads of q and k


def _attention(h=4, kv=2, hd=16, s=12, d=32, **extras):
    from flexflow_tpu.ops.attention import GroupedQueryAttention

    return GroupedQueryAttention("attn", _pc(3), Tensor((2, s, d)), h, kv,
                                 hd, hd ** -0.5, **extras)


def test_qk_norm_against_the_formula_written_out():
    from flexflow_tpu.ops.attention import grouped_causal_attention
    from flexflow_tpu.ops.seq_gated import apply_rope, rotary_table

    rule = {"rope_type": "default", "rope_theta": 1e6, "dim": 16}
    op = _attention(rope=rule, qk_norm=1e-5)
    params = op.init_params(jax.random.PRNGKey(2))
    assert list(params) == ["wq", "wk", "wv", "wo", "q_norm", "k_norm"]
    assert params["q_norm"].shape == params["k_norm"].shape == (16,)
    assert np.all(params["q_norm"] == 1) and np.all(params["k_norm"] == 1)
    # the four matrices are the operator's without the norms
    bare = _attention(rope=rule).init_params(jax.random.PRNGKey(2))
    for k, v in bare.items():
        np.testing.assert_array_equal(params[k], v)
    params = dict(params, q_norm=1 + 0.3 * _rand(3, 16),
                  k_norm=1 + 0.3 * _rand(4, 16))
    x = _rand(5, 2, 12, 32)
    before = _counted("attn.qk_norm")
    got, _ = op.forward(params, {}, [x], True)
    assert _counted("attn.qk_norm") == before + 1

    def normed(y, heads, gain):
        y = y.reshape(2, 12, heads, 16)
        y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + 1e-5)
        return y * gain

    cos, sin = rotary_table(rule, 12)
    q = apply_rope(normed(x @ params["wq"], 4, params["q_norm"]), cos, sin)
    k = apply_rope(normed(x @ params["wk"], 2, params["k_norm"]), cos, sin)
    want = grouped_causal_attention(
        q.reshape(2, 12, 64), k.reshape(2, 12, 32), x @ params["wv"], 4, 2,
        0.25) @ params["wo"]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # a head's norm is over its own 16 values: scaling one head of q's
    # columns changes nothing
    scaled = dict(params, wq=params["wq"].at[:, :16].multiply(7.0))
    np.testing.assert_allclose(op.forward(scaled, {}, [x], True)[0], got,
                               rtol=1e-4, atol=1e-5)
    # and it is the reference's layer
    c = _tiny_config(hidden_size=32, num_attention_heads=4,
                     num_key_value_heads=2)
    assert c["hidden_size"] // c["num_attention_heads"] == 8
    c = dict(c, hidden_size=64)     # heads of 16, as the operator's
    with jax.default_matmul_precision("highest"):
        for i in range(2):
            np.testing.assert_allclose(
                got[i], _reference().attention(params, x[i], c), rtol=1e-4,
                atol=1e-5)
    assert op.param_bytes() == _attention(rope=rule).param_bytes() + 4 * 32
    assert op.cost_signature()[-1] == ("qk_norm", 1e-5)
    assert op.flops_per_sample() == _attention(
        rope=rule).flops_per_sample() + 12 * 4.0 * 16 * 6


# the operators of granite_4_0_h_micro's and laguna_s_2_1's attention
# layers at their cells' shapes: the jaxpr of the forward and the gradient
# with the kernel gate open, as the parent commit (PR 37) traced them
# (granite's is the digest tests/test_rope_kernel.py pins), renewed when
# the VMEM count gave these shapes the fused flash backward (the split
# form still traces to 4cb7..., c533... and b9e3...)
_YARN = dict(rope_theta=500000, rope_type="yarn", factor=128,
             original_max_position_embeddings=8192, beta_slow=1,
             beta_fast=32, attention_factor=1.4852030263919618, dim=64)
_PARENTS = {
    "granite_4_0_h_micro": (
        (2, 8192, 2048), 32, 8, 64, 0.015625, {}, "b2dd7dcf59eda730"),
    "laguna_s_2_1.window": (
        (2, 8192, 3072), 72, 8, 128, 128 ** -0.5,
        dict(rope=dict(rope_type="default", rope_theta=10000, dim=128),
             window=512, gate=True), "5fab83af1b1f8295"),
    "laguna_s_2_1.full": (
        (2, 8192, 3072), 48, 8, 128, 128 ** -0.5,
        dict(rope=_YARN, gate=True), "4fc4e6abb823e8e0"),
}


@pytest.mark.parametrize("layer", sorted(_PARENTS))
def test_attention_without_the_norms_traces_to_the_parents_program(
        layer, pallas_kernels, monkeypatch):
    from flexflow_tpu.ops.attention import GroupedQueryAttention

    fa = importlib.import_module("flexflow_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_should_interpret", lambda: False)
    shape, heads, kv, hd, scale, extras, digest = _PARENTS[layer]
    op = GroupedQueryAttention("attn", _pc(3), Tensor(shape, "bfloat16"),
                               heads, kv, hd, scale, **extras)
    params = {k: jax.ShapeDtypeStruct(v, jnp.float32)
              for k, v in op._shapes().items()}
    assert op._gains() == ()
    before = _counted("attn.qk_norm")

    def step(params, x):
        return jax.value_and_grad(
            lambda p, x: op.forward(p, {}, [x], True)[0].astype(
                jnp.float32).sum(), (0, 1))(params, x)

    with pallas_kernels():
        text = str(jax.make_jaxpr(step)(
            params, jax.ShapeDtypeStruct(shape, jnp.bfloat16)))
    assert "ff_flash" in text and _counted("attn.qk_norm") == before
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


# ---------------------------------------------------------------------------
# the router


def _router(n_router, top_k, d, tokens, scale=1.0, **extras):
    from flexflow_tpu.ops.expert_share import TopKRouter

    op = TopKRouter("r", _pc(2), Tensor(tokens + (d,)), n_router, top_k,
                    scale, **extras)
    return op, op.init_params(jax.random.PRNGKey(3)), op.init_state()


def test_sigmoid_router_against_a_loop():
    """Sigmoid of each of 32 logits, the 4 largest of score + bias, the
    weights score / (sum of the four + 1e-6); the bias selects only and
    moves by rate * sign(mean load - load)."""
    op, params, state = _router(32, 4, 8, (2, 6), denominator_eps=1e-6)
    assert set(state) == {"bias"} and np.all(state["bias"] == 0)
    state = {"bias": _rand(7, 32, scale=0.2)}
    x = _rand(4, 2, 6, 8)
    gates, new_state = op.forward(params, state, [x], True)
    logits = np.asarray(x, np.float64) @ np.asarray(params["kernel"],
                                                    np.float64)
    bias = np.asarray(state["bias"], np.float64)
    load = np.zeros(32)
    moved = 0
    for b in range(2):
        for t in range(6):
            s = 1.0 / (1.0 + np.exp(-logits[b, t]))
            order = np.argsort(-(s + bias))
            assert (s + bias)[order[3]] - (s + bias)[order[4]] > 1e-6
            moved += set(order[:4]) != set(np.argsort(-s)[:4])
            want = np.zeros(32)
            want[order[:4]] = s[order[:4]] / (s[order[:4]].sum() + 1e-6)
            load[order[:4]] += 1
            np.testing.assert_allclose(gates[b, t], want, rtol=1e-5,
                                       atol=1e-7)
    assert moved > 0, "a bias this large selects other experts"
    np.testing.assert_allclose(
        new_state["bias"], bias + 1e-3 * np.sign(load.mean() - load),
        rtol=1e-6)
    # the weights carry the gradient, the selection and the bias none
    g = jax.grad(lambda k: jnp.sum(op.forward(
        {"kernel": k}, state, [x], True)[0] * _rand(5, 2, 6, 32)))(
            params["kernel"])
    assert np.all(np.isfinite(g)) and float(jnp.max(jnp.abs(g))) > 0
    # and it is the reference's rule, bias and all
    c = _config()
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            gates.reshape(-1, 32), _reference().router_weights(
                params["kernel"], x.reshape(-1, 8), c, state["bias"]),
            rtol=1e-5, atol=1e-7)
    # without the 1e-6 the weights sum to one exactly as they did
    plain, _, _ = _router(32, 4, 8, (2, 6))
    ones = jnp.sum(plain.forward(params, state, [x], True)[0], axis=-1)
    np.testing.assert_allclose(ones, 1.0, rtol=1e-6)
    assert float(jnp.max(jnp.sum(gates, axis=-1))) < 1.0


def test_moonlights_router_traces_to_the_parents_program():
    """sigmoid top 6 of 64 times 2.446 with no denominator eps at the
    Moonlight cell's shape: forward, state and gradient as the parent
    commit (PR 37) traced them."""
    from flexflow_tpu.ops.expert_share import TopKRouter

    op = TopKRouter("r", _pc(2), Tensor((2, 8192, 2048), "bfloat16"), 64,
                    6, 2.446)
    assert op.denominator_eps == 0.0

    def step(k, b, x):
        def f(k, x):
            g, st = op.forward({"kernel": k}, {"bias": b}, [x], True)
            return g.sum(), st
        return jax.value_and_grad(f, (0, 1), has_aux=True)(k, x)

    text = str(jax.make_jaxpr(step)(
        jax.ShapeDtypeStruct((2048, 64), jnp.float32),
        jax.ShapeDtypeStruct((64,), jnp.float32),
        jax.ShapeDtypeStruct((2, 8192, 2048), jnp.bfloat16)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == "607341fafd18c7b8"


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of 8 of 32 experts, the top 4 of 32 and no shared
    expert: the routed parts of all the shares are the uncut reference's
    layer output."""
    from flexflow_tpu.ops.expert_share import HeldExperts

    ref = _reference()
    c = _tiny_config(experts_held=[0, 32], num_experts=32,
                     router_outputs=32, num_experts_per_tok=4,
                     num_dense_layers=0)
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    tokens = (2, 24)
    x = _rand(14, *tokens, d)

    def experts(held):
        return HeldExperts("e", _pc(2), Tensor(tokens + (d,)),
                           Tensor(tokens + (32,)), f, held, 4)

    pw = experts((0, 32)).init_params(jax.random.PRNGKey(4))
    router, pr, st = _router(32, 4, d, tokens, c["routed_scaling_factor"],
                             denominator_eps=1e-6)
    gates = jax.jit(lambda x: router.forward(pr, st, [x], True)[0])(x)
    assert int(jnp.sum(gates > 0)) == 4 * 48
    flat = x.reshape(-1, d)
    params = {"blk1_moe_router": pr, "blk1_moe_experts": pw}

    @jax.jit
    def layer(x, gates):
        """The uncut reference, and each share beside what the reference
        gives for the same experts."""
        shares = []
        for lo in range(0, 32, 8):
            share = experts((lo, lo + 8))
            ps = {k: v[lo:lo + 8] for k, v in pw.items()}
            y, state = share.forward(ps, share.init_state(), [x, gates],
                                     True)
            shares.append((y.reshape(-1, d), state, ref.routed_part(
                ps, gates.reshape(-1, 32), flat,
                dict(c, experts_held=[lo, lo + 8]))))
        return ref.feed_forward(params, 1, flat, c), shares

    with jax.default_matmul_precision("highest"):
        want, shares = layer(x, gates)
    total, pairs = jnp.zeros_like(flat), 0
    for lo, (y, state, y_ref) in zip(range(0, 32, 8), shares):
        assert float(state["dropped"]) == 0
        pairs += int(jnp.sum(state["counts"][lo:lo + 8]))
        np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-5)
        total = total + y
    assert pairs == 4 * 48, "every selection falls in exactly one share"
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# the model class


@pytest.fixture(scope="module")
def tiny_model():
    from flexflow_tpu.models.lfm2 import Lfm2Config, Lfm2LM

    return Lfm2LM(Lfm2Config.from_config(
        _tiny_config(), batch_size=2, seq_length=20),
        MachineModel(jax.devices()[:1]))


def test_model_class_builds_the_named_operators(tiny_model):
    names = [op.name for op in tiny_model.layers]
    assert names == [
        "embed",
        "blk0_norm1", "blk0_conv", "blk0_res1", "blk0_norm2", "blk0_ffn",
        "blk0_res2",
        "blk1_norm1", "blk1_attn_full", "blk1_res1", "blk1_norm2",
        "blk1_moe_router", "blk1_moe_experts", "blk1_res2",
        "blk2_norm1", "blk2_conv", "blk2_res1", "blk2_norm2",
        "blk2_moe_router", "blk2_moe_experts", "blk2_res2",
        "final_norm", "lm_head", "softmax"]
    by_name = {op.name: op for op in tiny_model.layers}
    attn, conv = by_name["blk1_attn_full"], by_name["blk0_conv"]
    assert (attn.num_heads, attn.num_kv_heads, attn.head_dim) == (4, 2, 16)
    assert attn.qk_norm == 1e-5 and attn.window is None and not attn.gate
    assert attn.rope == {"rope_type": "default", "rope_theta": 1000000,
                         "dim": 16}
    assert attn.scale == 0.25 and conv.taps == 3
    router = by_name["blk1_moe_router"]
    assert (router.score, router.n_router, router.top_k, router.scale) \
        == ("sigmoid", 16, 3, 1.0)
    assert router.denominator_eps == 1e-6
    assert router.bias_update_rate == 1e-3
    assert by_name["blk1_moe_experts"].experts_held == (4, 8)
    assert by_name["blk0_ffn"].d_ff == 128
    # tied: the head reads the embedding's own matrix under its key
    assert by_name["lm_head"].param_key == "embed"
    assert len(tiny_model.recompute_blocks) == 3
    params, state = tiny_model.init(1)
    assert "lm_head" not in params
    assert set(state["blk1_moe_router"]) == {"bias"}
    # the published model: both leading dense layers, 3 conv to 1 attention
    from flexflow_tpu.models.lfm2 import Lfm2Config

    t = Lfm2Config.from_config(_config())
    kinds = t.layer_types[:t.num_layers]
    assert kinds == ("conv", "conv", "full_attention", "conv", "conv",
                     "conv", "full_attention", "conv")
    assert (t.num_dense_layers, t.head_dim, t.conv_L_cache) == (2, 64, 3)
    assert len(t.layer_types) == 24 and t.layer_types.count("conv") == 18


@pytest.mark.parametrize("head", ["plain", "fused"])
def test_loss_and_every_operator_gradient_against_the_reference(
        head, tiny_model, pallas_kernels):
    """Seeded weights; ``fused``: a model of whole lanes with the kernel
    gate open, so that the head runs in the fused projection+CE kernel,
    the attention in the ``pack2`` flash kernels behind its norms and
    rotary, and the held experts in ``ff_gmm``, inside recomputed
    blocks."""
    from flexflow_tpu.models.lfm2 import Lfm2Config, Lfm2LM

    ff, cfg, b, s = tiny_model, _tiny_config(), 2, 20
    kernels = contextlib.nullcontext
    if head == "fused":
        cfg, b, s = _tiny_config(
            hidden_size=128, num_attention_heads=2, num_key_value_heads=1,
            intermediate_size=256, moe_intermediate_size=128,
            vocab_size=256, max_position_embeddings=512), 4, 512
        ff = Lfm2LM(Lfm2Config.from_config(
            cfg, batch_size=b, seq_length=s),
            MachineModel(jax.devices()[:1]))
        kernels = pallas_kernels
        with kernels():
            assert ff._lm_head_fusion()
    packed = _counted("kernels.flash.pack2.fused")
    plain = lm_check.check_against_reference(ff, cfg, _reference(), b, s,
                                             kernels)
    assert "lm_head" not in plain
    if head == "fused":
        assert _counted("kernels.flash.pack2.fused") == packed + 1
        assert _counted("kernels.rope.xla.2x64r64") >= 1
        from flexflow_tpu import obs

        assert not any(k.startswith("kernels.rope.pallas.") and "x64r" in k
                       for k in obs.snapshot()["counters"])


def test_recomputed_step_trains_and_counts_the_mechanisms(tiny_model):
    model = tiny_model
    toks = jax.random.randint(jax.random.PRNGKey(7), (2, 20), 0, 96)
    step, (p, st), losses = model.make_train_step(), model.init(3), []
    for _ in range(8):
        p, st, _, loss = step(p, st, None, toks, toks)
        losses.append(float(loss))
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert float(st["blk1_moe_experts"]["dropped"]) == 0
    # the selection bias moved: it is state the step carries
    assert float(jnp.max(jnp.abs(st["blk1_moe_router"]["bias"]))) > 0
    assert _counted("moe.experts_held") == 4
    assert _counted("conv.taps") == 3
    assert _counted("kernels.short_conv.xla.64x3") >= 2
    assert _counted("attn.qk_norm") >= 1
    assert _counted("attn.kv_groups.2") >= 1
    assert _counted("kernels.rope.xla.4x16r16") >= 1
    assert _counted("runtime.recomputed_blocks") >= 3


def test_parameters_are_the_count_the_issue_reckons():
    """772.2 M parameters at the cell's size and 8.340 B whole, from the
    operators' own ``param_bytes`` (no array is made)."""
    from flexflow_tpu.models.lfm2 import Lfm2Config, Lfm2LM

    def count(**over):
        ff = Lfm2LM(Lfm2Config.from_config(
            _config(**over), batch_size=2, seq_length=8192),
            MachineModel(jax.devices()[:1]))
        return ff, {op.name: op.param_bytes() // 4 for op in ff.layers}

    ff, by_op = count()
    assert by_op["blk0_conv"] == 2048 * 6144 + 2048 * 2048 + 2048 * 3 \
        == 16_783_360
    assert by_op["blk2_attn_full"] \
        == 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64 == 10_485_888
    assert by_op["blk0_ffn"] == by_op["blk1_ffn"] == 3 * 2048 * 7168 \
        == 44_040_192
    assert by_op["blk2_moe_router"] == 2048 * 32
    assert by_op["blk2_moe_experts"] == 8 * 3 * 2048 * 1792 == 88_080_384
    assert by_op["embed"] == 16384 * 2048 and by_op["lm_head"] == 0
    assert "blk1_moe_router" not in by_op and "blk2_ffn" not in by_op
    assert sum(by_op.values()) == 772_217_088
    experts = next(op for op in ff.layers if op.name == "blk2_moe_experts")
    assert experts.rows_capacity == 32768
    attn = next(op for op in ff.layers if op.name == "blk2_attn_full")
    assert attn.flops_per_sample() == pytest.approx(
        8192 * (2.0 * 10_485_760 + 4.0 * 32 * 64 * 8193 / 2
                + 4.0 * 64 * 40))
    # the whole model: 24 layers, 32 experts held, 65536 rows
    _, whole = count(num_layers=24, num_experts=32, experts_held=[0, 32],
                     vocab_size=65536)
    assert sum(whole.values()) == (
        65536 * 2048 + 18 * 16_783_360 + 6 * 10_485_888 + 2 * 44_040_192
        + 22 * (2048 * 32 + 32 * 3 * 2048 * 1792) + (2 * 24 + 1) * 2048) \
        == 8_339_929_856
    assert round(sum(whole.values()) / 1e9, 3) == 8.340


@pytest.mark.parametrize("key,value", [
    ("conv_bias", True), ("norm_topk_prob", False),
    ("use_expert_bias", False), ("tie_word_embeddings", False),
    ("layer_types", ["conv", "sliding_attention"] * 12),
    ("layer_types", ["conv"] * 4), ("experts_held", [0, 16]),
    ("num_attention_heads", 24)])
def test_from_config_refuses_what_the_class_does_not_build(key, value):
    from flexflow_tpu.models.lfm2 import Lfm2Config

    Lfm2Config.from_config(_config())                   # the file passes
    with pytest.raises(ValueError, match=key):
        Lfm2Config.from_config(_config(**{key: value}))
    with pytest.raises(ValueError, match="plain SGD"):
        Lfm2Config.from_config(_config(optimizer={"kind": "adamw"}))


def test_apps_lm_trains_the_model_from_its_configuration_file():
    from flexflow_tpu.apps import lm

    assert "lfm2_moe" in lm.MODEL_TYPES
    lines = []
    out = lm.main(["--model-config", CONFIG, "--preset", "rehearsal",
                   "-b", "2", "-s", "32", "-i", "12", "--seed", "5"],
                  log=lines.append)
    assert any("2 conv of 3 taps, 1 attention" in l and "1 dense" in l
               and "[4, 8) of 16 held" in l for l in lines[:2])
    losses = out["loss"]
    # two batches in turn: the loss starts near ln(vocabulary) and falls
    assert len(losses) == 12 and np.all(np.isfinite(losses))
    assert abs(losses[0] - np.log(96)) < 0.5 and losses[-1] < losses[0]
    # the fit path publishes the expert layers' state
    assert _counted("moe.dropped_pairs") == 0
    assert _counted("moe.load_max_over_mean") >= 1.0
    with pytest.raises(SystemExit, match="positions"):
        lm.main(["--model-config", CONFIG, "--preset", "rehearsal",
                 "-s", "64", "-i", "1"], log=lines.append)


# ---------------------------------------------------------------------------
# rotary positions at a head the kernel refuses, on the TPU


_ROPES = [(4, 64, {"rope_type": "default", "rope_theta": 1e6, "dim": 64}),
          (3, 16, {"rope_type": "default", "rope_theta": 1e4, "dim": 8}),
          (2, 128, dict(_YARN))]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads,hd,rule", _ROPES,
                         ids=["whole", "partial", "yarn"])
def test_rotary_by_products_is_apply_rope_bit_for_bit(heads, hd, rule,
                                                      dtype):
    """The two 0/1 products and the float32 pass on (B, S, heads * hd)
    give ``apply_rope``'s numbers exactly, whole, partial and YaRN; the
    gradient is the rotation the other way round."""
    from flexflow_tpu.ops.seq_gated import (apply_rope, rope_by_products,
                                            rotary_table)

    x = _rand(heads, 2, 24, heads * hd).astype(dtype)
    cos, sin = rotary_table(rule, 24)

    def on_view(x):
        return apply_rope(x.reshape(2, 24, heads, hd), cos,
                          sin).reshape(2, 24, heads * hd)

    got = rope_by_products(x, cos, sin, heads)
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(on_view(x), np.float32))
    weight = _rand(9, 2, 24, heads * hd)
    g1, g2 = (jax.grad(lambda x, f=f: jnp.sum(
        f(x).astype(jnp.float32) * weight))(x)
        for f in (lambda x: rope_by_products(x, cos, sin, heads), on_view))
    np.testing.assert_allclose(np.asarray(g1, np.float32),
                               np.asarray(g2, np.float32),
                               rtol=1e-2 if dtype == "bfloat16" else 1e-6,
                               atol=3e-2 if dtype == "bfloat16" else 1e-6)


def test_operator_takes_the_products_where_the_kernel_refuses_on_the_tpu(
        pallas_kernels, monkeypatch):
    """Under the kernel gate a head of 64 is turned by
    ``rope_by_products`` (no 4-D view on the TPU), a head of 128 by the
    kernel, and off the TPU every head by ``apply_rope``; the counter
    names the XLA path either way."""
    from flexflow_tpu.ops import seq_gated

    seen = []
    real = seq_gated.rope_by_products
    monkeypatch.setattr(
        seq_gated, "rope_by_products",
        lambda x, cos, sin, heads: seen.append(heads) or real(
            x, cos, sin, heads))

    def trace(hd):
        rule = {"rope_type": "default", "rope_theta": 1e6, "dim": hd}
        op = _attention(h=4, kv=2, hd=hd, s=128, d=128, rope=rule,
                        qk_norm=1e-5)
        params = op.init_params(jax.random.PRNGKey(1))
        return jax.eval_shape(
            lambda p, x: op.forward(p, {}, [x], True)[0], params,
            jax.ShapeDtypeStruct((2, 128, 128), jnp.bfloat16))

    before = _counted("kernels.rope.xla.4x64r64")
    trace(64)
    assert seen == [] and _counted("kernels.rope.xla.4x64r64") == before + 1
    with pallas_kernels():
        trace(64)
        assert seen == [4, 2]
        assert _counted("kernels.rope.xla.4x64r64") == before + 2
        trace(128)
        assert seen == [4, 2] and _counted("kernels.rope.pallas.4x128r128")
