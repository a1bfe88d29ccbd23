"""What Laguna-S-2.1 forced (PR 34): a sliding window in the flash kernels
and on the plain path, rotary positions (partial, YaRN), a per-head gate
and a head count a layer in ``GroupedQueryAttention``, a softmax score
rule in the one router class, and the model class ``apps/lm.py`` trains
from a ``laguna`` configuration: each against plain ``jax.numpy`` or a loop
over positions, the whole model against
``benchmarks/reference/laguna_s_2_1.py``, and what Granite's, Moonlight's
and GPT-2's cells run held to what it was."""

import contextlib
import functools
import hashlib
import importlib
import json
import math
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
CONFIG = os.path.join(ROOT, "benchmarks", "configs", "laguna_s_2_1.json")


def _pc(rank):
    return ParallelConfig((1,) * rank, (0,))


def _rand(seed, *shape, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


def _reference():
    from benchmarks.reference import laguna_s_2_1

    return laguna_s_2_1


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


def _flash():
    return importlib.import_module("flexflow_tpu.ops.pallas.flash_attention")


# ---------------------------------------------------------------------------
# the window


def _attention_by_loop(q, k, v, window, scale):
    """(B, H, S, d): one query position after another, each against the
    keys ``t <= i`` with ``i - t < window`` and no others."""
    b, h, s, _ = q.shape
    rows = []
    for i in range(s):
        first = 0 if window is None else max(0, i - window + 1)
        scores = jnp.einsum("bhd,bhkd->bhk", q[:, :, i],
                            k[:, :, first:i + 1]) * scale
        rows.append(jnp.einsum("bhk,bhkd->bhd", jax.nn.softmax(scores, -1),
                               v[:, :, first:i + 1]))
    return jnp.stack(rows, axis=2)


# (sequence, window, block, pieces): a window shorter than the sequence
# that the blocks divide (pieces on both cut tiles), that they do not
# (whole-tile masks), of one position, equal to and longer than the
# sequence (the causal call), a sequence no block divides (padded rows
# past the window of every key), two blocks to a window
_WINDOWS = [(64, 16, 16, 8), (64, 12, 16, 8), (64, 1, 16, 8),
            (32, 32, 16, 8), (32, 50, 16, 8), (52, 5, 16, 8),
            (40, 16, 16, 4), (96, 32, 16, 8), (48, 16, None, 8)]


def _operands(s):
    return tuple(_rand(i + s, 2, 2, s, 16) for i in range(4))


def _outputs_and_grads(attend, q, k, v, w):
    # the gradients of sum(attend * w): its vjp of w
    out, vjp = jax.vjp(attend, q, k, v)
    return out, jnp.sum(out * w), vjp(w)


@functools.lru_cache(maxsize=None)
def _by_loop(s, window):
    """The loop's result, its weighted sum and gradients: one reading
    for both backward forms."""
    return jax.jit(_outputs_and_grads, static_argnums=0)(
        lambda q, k, v: _attention_by_loop(q, k, v, window, 0.25),
        *_operands(s))


@pytest.mark.parametrize("backward", ["fused", "split"])
@pytest.mark.parametrize("s,window,block,piece", _WINDOWS)
def test_windowed_flash_against_a_loop_over_positions(s, window, block,
                                                      piece, backward,
                                                      monkeypatch):
    """Forward and both backward forms under the interpreter."""
    fa = _flash()
    monkeypatch.setattr(fa, "_WINDOW_FWD_PIECE", piece)
    monkeypatch.setattr(fa, "_WINDOW_BWD_PIECE", piece)
    monkeypatch.setattr(fa, "_FUSED_BWD_BYTES",
                        2 ** 21 if backward == "fused" else 64)
    fa._make_flash.cache_clear()
    try:
        name = f"kernels.flash.pad128.{backward}" \
            + (f".w{window}" if window < s else "")
        before = _counted(name)

        def kernels(q, k, v):
            return fa.flash_attention(q, k, v, True, block, block,
                                      window=window)

        out, got, grads = jax.jit(_outputs_and_grads, static_argnums=0)(
            kernels, *_operands(s))
        assert _counted(name) == before + 1
        want_out, want, want_grads = _by_loop(s, window)
        np.testing.assert_allclose(got, want, rtol=1e-5)
        np.testing.assert_allclose(out, want_out, rtol=1e-4, atol=1e-5)
        for g, wg in zip(grads, want_grads):
            np.testing.assert_allclose(g, wg, rtol=1e-3, atol=1e-5)
    finally:
        fa._make_flash.cache_clear()


def test_window_is_refused_where_it_means_nothing():
    fa = _flash()
    q = _rand(0, 1, 2, 16, 16)
    with pytest.raises(ValueError, match="causal self-attention"):
        fa.flash_attention(q, q, q, False, window=4)
    with pytest.raises(ValueError, match="causal self-attention"):
        fa.flash_attention(q, q[:, :, :8], q[:, :, :8], True, window=4)


def test_windowed_tiles_walk_the_band_and_nothing_left_of_it():
    """8192 positions under a window of 512 at the rule's blocks: two
    inner steps a block where the causal call walks sixteen, both tiles
    square and in pieces; the block rule is the window's."""
    fa = _flash()
    assert fa._pick_block(8192, False, 512) == 512
    assert fa._pick_block(8192, False, 4096) == 1024
    assert fa._pick_block(8192, False, 384) == 128
    assert fa._pick_block(8192, False, None) == 1024
    assert fa._pick_block(8192, False, 8192) == 1024       # no window
    t = fa._Tiles(True, 8192, 512, 512, 16, 16, 256, 512)
    assert (t.inner_q, t.inner_k, t.n_sub) == (2, 2, 2)
    whole = fa._Tiles(True, 8192, 512, 512, 16, 16, 256)
    assert (whole.inner_q, whole.inner_k) == (16, 16)
    live = [(q, k) for q in range(16) for k in range(16)
            if bool(t.live(q, k))]
    assert live == [(q, k) for q in range(16) for k in (q - 1, q) if k >= 0]
    # every live tile is a step of the band, in both orders
    assert {(q, int(t.k_at(q, j))) for q in range(16) for j in range(2)} \
        >= set(live)
    assert {(int(t.q_at(k, j)), k) for k in range(16) for j in range(2)} \
        >= set(live)
    # a window the blocks do not divide: masks over whole tiles
    assert fa._Tiles(True, 64, 16, 16, 4, 4, 8, 12).n_sub == 1


@pytest.mark.parametrize("window", [5, 24, 40])
def test_plain_path_window_against_a_loop_over_positions(window):
    """``grouped_causal_attention`` (the path off the TPU): 6 query heads
    on 2, a window shorter than, equal to and longer than 24 positions."""
    from flexflow_tpu.ops.attention import grouped_causal_attention

    b, s, h, kv, hd = 2, 24, 6, 2, 8
    q, k, v = _rand(1, b, s, h * hd), _rand(2, b, s, kv * hd), \
        _rand(3, b, s, kv * hd)
    got = grouped_causal_attention(q, k, v, h, kv, 0.3, window)

    def heads(x, n):
        return x.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

    want = jax.jit(_attention_by_loop, static_argnums=(3, 4))(
        heads(q, h), jnp.repeat(heads(k, kv), h // kv, axis=1),
        jnp.repeat(heads(v, kv), h // kv, axis=1), window, 0.3)
    np.testing.assert_allclose(
        got, want.transpose(0, 2, 1, 3).reshape(b, s, h * hd), rtol=1e-5,
        atol=1e-6)


# ---------------------------------------------------------------------------
# rotary rules


_YARN = dict(rope_theta=500000, rope_type="yarn", factor=128,
             original_max_position_embeddings=8192, beta_slow=1,
             beta_fast=32, attention_factor=1.4852030263919618)


def test_yarn_frequencies_against_the_formula_written_out():
    """Laguna's full layers: 64 of 128 dimensions turn; pairs below the
    first correction dimension keep theta^(-2i/64), pairs above the second
    turn 128 times slower, a linear blend between; cos and sin carry the
    attention factor at every length."""
    from flexflow_tpu.ops.seq_gated import rotary_table

    cos, sin = rotary_table(dict(_YARN, dim=64), 16)
    assert cos.shape == sin.shape == (16, 32)
    theta, dim = 500000.0, 64
    low = math.floor(dim * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(dim * math.log(8192 / (1 * 2 * math.pi))
                     / (2 * math.log(theta)))
    assert (low, high) == (9, 18)
    for i in range(32):
        plain = theta ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        inv = plain * (1 - ramp) + plain / 128 * ramp
        for p in (0, 1, 7, 15):
            assert float(cos[p, i]) == pytest.approx(
                1.4852030263919618 * math.cos(p * inv), abs=2e-6)
            assert float(sin[p, i]) == pytest.approx(
                1.4852030263919618 * math.sin(p * inv), abs=2e-6)
    assert 0.1 * math.log(128) + 1 == pytest.approx(_YARN["attention_factor"])
    # the reference's own frequencies, written independently, agree
    inv, grow = _reference().rotary_frequencies(
        dict(_YARN, partial_rotary_factor=0.5), 128)
    np.testing.assert_allclose(
        cos, grow * np.cos(np.arange(16)[:, None] * np.asarray(inv)),
        rtol=1e-5, atol=1e-6)
    # the default rule is rope_angles
    from flexflow_tpu.ops.seq_gated import rope_angles

    for got, want in zip(rotary_table({"dim": 128, "rope_theta": 10000},
                                      16), rope_angles(16, 128, 10000.0)):
        np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="rope_type"):
        rotary_table({"dim": 8, "rope_theta": 1e4, "rope_type": "ntk"}, 4)


def test_partial_rotary_turns_the_leading_dimensions_alone():
    from flexflow_tpu.ops.seq_gated import apply_rope, rope_angles

    x = _rand(3, 2, 10, 3, 16)
    cos, sin = rope_angles(10, 8, 100.0)
    got = apply_rope(x, cos, sin)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_array_equal(got[..., :8],
                                  apply_rope(x[..., :8], cos, sin))
    # scores between rotated vectors depend on the distance alone
    q, k = apply_rope(_rand(4, 1, 10, 16)[:, :1].repeat(10, 1), cos, sin), \
        apply_rope(_rand(5, 1, 10, 16)[:, :1].repeat(10, 1), cos, sin)
    scores = jnp.einsum("bqd,bkd->qk", q, k)
    assert float(scores[5, 3]) == pytest.approx(float(scores[9, 7]),
                                                rel=1e-4)


# ---------------------------------------------------------------------------
# the operator


def _laguna_attention(window, h=6, kv=2, hd=16, s=24, d=32, rope=None):
    from flexflow_tpu.ops.attention import GroupedQueryAttention

    rope = rope or dict(_YARN, dim=hd // 2)
    return GroupedQueryAttention(
        "attn", _pc(3), Tensor((2, s, d), "float32"), h, kv, hd, hd ** -0.5,
        rope=rope, window=window, gate=True)


@pytest.mark.parametrize("path", ["xla", "kernels"])
@pytest.mark.parametrize("window", [None, 8])
def test_attention_operator_against_the_reference(window, path,
                                                  pallas_kernels):
    """Rotary (partial YaRN on a full layer, default on a sliding one),
    window and per-head gate together, values and every weight's gradient,
    the gate's among them; with the kernel gate open the scores run in
    the flash kernels."""
    ref = _reference()
    kind = "full_attention" if window is None else "sliding_attention"
    c = _tiny_config(hidden_size=32, sliding_window=8,
                     layer_types=[kind],
                     num_attention_heads_per_layer=[6])
    rule = dict(c["rope_parameters"][kind])
    rule["dim"] = int(16 * rule.pop("partial_rotary_factor"))
    op = _laguna_attention(window, rope=rule)
    params = op.init_params(jax.random.PRNGKey(0))
    assert set(params) == {"wq", "wk", "wv", "wo", "wg"}
    assert params["wg"].shape == (32, 6)
    x, w = _rand(1, 2, 24, 32), _rand(2, 2, 24, 32)
    groups = _counted("attn.kv_groups.3")
    with pallas_kernels() if path == "kernels" else contextlib.nullcontext():
        got, grads = jax.jit(jax.value_and_grad(
            lambda p: jnp.sum(op.forward(p, {}, [x], True)[0] * w)))(params)
    assert _counted("attn.kv_groups.3") == groups + 1
    assert _counted("attn.kv_groups") == 3
    if window:
        assert _counted("attn.window") == 8

    def plain(p):
        return jnp.stack([ref._attention(p, row, c, 0) for row in x])

    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: jnp.sum(plain(p) * w)))(params)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for key in params:
        np.testing.assert_allclose(grads[key], want_grads[key], rtol=2e-3,
                                   atol=2e-5, err_msg=key)
    assert float(jnp.max(jnp.abs(grads["wg"]))) > 1e-3
    assert op.param_bytes() == 4 * (2 * 32 * 96 + 2 * 32 * 32 + 32 * 6)
    with pytest.raises(ValueError, match="rotary dimensions"):
        _laguna_attention(None, rope={"dim": 32, "rope_theta": 1e4})


def test_gate_multiplies_each_head_before_the_output_projection():
    """A gate matrix of zeros is sigmoid 0 = 0.5 on every head: half the
    ungated result; its gradient is that of the formula."""
    from flexflow_tpu.ops.attention import GroupedQueryAttention

    plain = GroupedQueryAttention("a", _pc(3), Tensor((2, 12, 32)), 4, 2,
                                  8, 0.3)
    gated = GroupedQueryAttention("a", _pc(3), Tensor((2, 12, 32)), 4, 2,
                                  8, 0.3, gate=True)
    params = plain.init_params(jax.random.PRNGKey(1))
    x = _rand(2, 2, 12, 32)
    base, _ = plain.forward(params, {}, [x], True)
    half, _ = gated.forward(dict(params, wg=jnp.zeros((32, 4))), {}, [x],
                            True)
    np.testing.assert_allclose(half, 0.5 * base, rtol=1e-5, atol=1e-7)
    # d/dwg at 0: x^T (0.25 * <head's result, head's rows of wo cotangent>)
    g = jax.grad(lambda wg: jnp.sum(gated.forward(
        dict(params, wg=wg), {}, [x], True)[0]))(jnp.zeros((32, 4)))
    eps = 1e-3
    bump = jnp.zeros((32, 4)).at[3, 1].set(eps)
    up = jnp.sum(gated.forward(dict(params, wg=bump), {}, [x], True)[0])
    down = jnp.sum(gated.forward(dict(params, wg=-bump), {}, [x], True)[0])
    assert float(g[3, 1]) == pytest.approx(float(up - down) / (2 * eps),
                                           rel=2e-2)


# ---------------------------------------------------------------------------
# the router, the shares


def _router(n_router, top_k, d, tokens, scale=1.0, score="softmax"):
    from flexflow_tpu.ops.expert_share import TopKRouter

    op = TopKRouter("r", _pc(2), Tensor(tokens + (d,)), n_router, top_k,
                    scale, score=score)
    return op, op.init_params(jax.random.PRNGKey(3)), op.init_state()


def test_softmax_router_against_a_loop():
    """Softmax over all 16, the 3 largest, renormalised, times 2.5; random
    float32 logits have no ties.  No state, no bias."""
    op, params, state = _router(16, 3, 8, (2, 5), 2.5)
    assert state == {} and set(params) == {"kernel"}
    x = _rand(4, 2, 5, 8)
    gates, new_state = op.forward(params, state, [x], True)
    assert new_state == {} and gates.shape == (2, 5, 16)
    logits = np.asarray(x, np.float64) @ np.asarray(params["kernel"],
                                                    np.float64)
    for b in range(2):
        for t in range(5):
            p = np.exp(logits[b, t] - logits[b, t].max())
            p /= p.sum()
            order = np.argsort(-p)
            assert p[order[2]] - p[order[3]] > 1e-6        # no tie
            want = np.zeros(16)
            want[order[:3]] = 2.5 * p[order[:3]] / p[order[:3]].sum()
            np.testing.assert_allclose(gates[b, t], want, rtol=1e-5,
                                       atol=1e-7)
    # the weights carry the gradient, the selection none
    g = jax.grad(lambda k: jnp.sum(op.forward(
        {"kernel": k}, {}, [x], True)[0] * _rand(5, 2, 5, 16)))(
            params["kernel"])
    assert np.all(np.isfinite(g)) and float(jnp.max(jnp.abs(g))) > 0
    # and it is the reference's rule
    c = _tiny_config(num_experts_per_tok=3, moe_routed_scaling_factor=2.5)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            gates.reshape(-1, 16), _reference().router_weights(
                params["kernel"], x.reshape(-1, 8), c), rtol=1e-5,
            atol=1e-7)
    from flexflow_tpu.ops.expert_share import TopKRouter
    with pytest.raises(ValueError, match="score rule"):
        TopKRouter("r", _pc(2), Tensor((2, 5, 8)), 16, 3, 1.0,
                   score="tanh")


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of 4 of 16 experts: the routed parts of all the shares,
    plus the shared expert counted once, are the uncut reference's layer
    output."""
    from flexflow_tpu.ops.expert_share import HeldExperts

    ref = _reference()
    c = _tiny_config(experts_held=[0, 16], num_experts=16)
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    n_router, top_k = c["router_outputs"], c["num_experts_per_tok"]
    assert (n_router, top_k) == (16, 3)
    tokens = (2, 16)
    x = _rand(14, *tokens, d)

    def experts(held):
        return HeldExperts("e", _pc(2), Tensor(tokens + (d,)),
                           Tensor(tokens + (n_router,)), f, held, top_k)

    pw = experts((0, 16)).init_params(jax.random.PRNGKey(4))
    router, pr, st = _router(n_router, top_k, d, tokens,
                             c["moe_routed_scaling_factor"])
    gates = jax.jit(lambda x: router.forward(pr, st, [x], True)[0])(x)
    shared = {k: _rand(15 + i, *shape, scale=0.1) for i, (k, shape) in
              enumerate([("w_gate", (d, f)), ("w_up", (d, f)),
                         ("w_down", (f, d))])}
    flat = x.reshape(-1, d)
    params = {"blk1_moe_router": pr, "blk1_moe_shared": shared,
              "blk1_moe_experts": pw}

    @jax.jit
    def layer(x, gates):
        """The uncut reference, its shared expert, and each share beside
        what the reference gives for the same experts."""
        shares = []
        for lo in range(0, n_router, 4):
            share = experts((lo, lo + 4))
            ps = {k: v[lo:lo + 4] for k, v in pw.items()}
            y, state = share.forward(ps, share.init_state(), [x, gates],
                                     True)
            shares.append((y.reshape(-1, d), state["dropped"],
                           ref.routed_part(
                               ps, gates.reshape(-1, n_router), flat,
                               dict(c, experts_held=[lo, lo + 4]))))
        return ref.feed_forward(params, 1, flat, c), \
            ref._glu(flat, **shared), shares

    with jax.default_matmul_precision("highest"):
        want, total, shares = layer(x, gates)
    for y, dropped, y_ref in shares:
        assert float(dropped) == 0
        np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-5)
        total = total + y
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# the model class


@pytest.fixture(scope="module")
def tiny_model():
    from flexflow_tpu.models.laguna import LagunaConfig, LagunaLM

    return LagunaLM(LagunaConfig.from_config(
        _tiny_config(), batch_size=2, seq_length=20),
        MachineModel(jax.devices()[:1]))


def test_model_class_builds_the_named_operators(tiny_model):
    names = [op.name for op in tiny_model.layers]
    for name in ("blk0_attn_full", "blk0_ffn", "blk1_attn_window",
                 "blk1_moe_router", "blk1_moe_experts", "blk1_moe_shared",
                 "blk2_attn_window", "lm_head"):
        assert name in names
    assert "blk0_moe_router" not in names and "blk1_ffn" not in names
    by_name = {op.name: op for op in tiny_model.layers}
    full, win = by_name["blk0_attn_full"], by_name["blk1_attn_window"]
    assert (full.num_heads, win.num_heads) == (4, 6)
    assert (full.window, win.window) == (None, 8)
    assert full.gate and win.gate
    assert full.rope["rope_type"] == "yarn" and full.rope["dim"] == 8
    assert win.rope["rope_type"] == "default" and win.rope["dim"] == 16
    assert by_name["blk1_moe_router"].score == "softmax"
    assert by_name["blk1_moe_experts"].experts_held == (4, 8)
    assert len(tiny_model.recompute_blocks) == 3
    _, state = tiny_model.init(1)
    assert "blk1_moe_router" not in state       # no bias, no state


@pytest.mark.parametrize("head", ["plain", "fused"])
def test_loss_and_every_operator_gradient_against_the_reference(
        head, tiny_model, pallas_kernels, monkeypatch):
    """Seeded weights; ``fused``: a model of whole lanes with the kernel
    gate open, so that the head runs in the fused projection+CE kernel
    and the attention in the flash kernels, windowed and full, inside
    recomputed blocks."""
    from flexflow_tpu.models.laguna import LagunaConfig, LagunaLM

    ff, cfg, b, s = tiny_model, _tiny_config(), 2, 20
    kernels = contextlib.nullcontext
    if head == "fused":
        fa = _flash()
        monkeypatch.setattr(fa, "_WINDOW_BLOCKS", (64, 32))
        monkeypatch.setattr(fa, "_WINDOW_FWD_PIECE", 16)
        monkeypatch.setattr(fa, "_WINDOW_BWD_PIECE", 32)
        cfg, b, s = _tiny_config(
            hidden_size=128, head_dim=128, num_key_value_heads=1,
            num_attention_heads_per_layer=[1, 2, 2], sliding_window=64,
            vocab_size=256, max_position_embeddings=512), 4, 512
        ff = LagunaLM(LagunaConfig.from_config(
            cfg, batch_size=b, seq_length=s),
            MachineModel(jax.devices()[:1]))
        kernels = pallas_kernels
        with kernels():
            assert ff._lm_head_fusion()
    windowed = _counted("kernels.flash.pack1.fused.w64")
    lm_check.check_against_reference(ff, cfg, _reference(), b, s, kernels)
    if head == "fused":
        assert _counted("kernels.flash.pack1.fused.w64") == windowed + 2


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
    assert _counted("attn.kv_groups.2") >= 1 \
        and _counted("attn.kv_groups.3") >= 2


def test_parameters_are_the_count_the_issue_reckons():
    """811 M parameters at published widths, from the operators' own
    ``param_bytes`` (no array is made)."""
    from flexflow_tpu.models.laguna import LagunaConfig, LagunaLM

    ff = LagunaLM(LagunaConfig.from_config(
        _config(), batch_size=2, seq_length=8192),
        MachineModel(jax.devices()[:1]))
    by_op = {op.name: op.param_bytes() // 4 for op in ff.layers}
    # q and o at the layer's heads, k and v at 8, the gate hidden x heads
    assert by_op["blk0_attn_full"] == by_op["blk4_attn_full"] \
        == 2 * 3072 * 6144 + 2 * 3072 * 1024 + 3072 * 48 == 44_187_648
    assert by_op["blk1_attn_window"] \
        == 2 * 3072 * 9216 + 2 * 3072 * 1024 + 3072 * 72 == 63_135_744
    assert by_op["blk0_ffn"] == 3 * 3072 * 12288 == 113_246_208
    assert by_op["blk1_moe_router"] == 3072 * 256
    assert by_op["blk1_moe_shared"] == 3 * 3072 * 1024 == 9_437_184
    assert by_op["blk1_moe_experts"] == 8 * 9_437_184
    assert by_op["embed"] == by_op["lm_head"] == 12544 * 3072
    sliding = sum(v for k, v in by_op.items() if k.startswith("blk1_"))
    assert round(sliding / 1e6, 1) == 148.9
    assert sum(by_op.values()) == 811_017_216
    experts = next(op for op in ff.layers if op.name == "blk1_moe_experts")
    assert experts.rows_capacity == 10240
    win = next(op for op in ff.layers if op.name == "blk1_attn_window")
    full = next(op for op in ff.layers if op.name == "blk0_attn_full")
    # a windowed query meets min(i + 1, 512) keys
    met = (512 * 513 // 2 + (8192 - 512) * 512) / 8192
    assert win.flops_per_sample() == pytest.approx(
        8192 * (2.0 * 63_135_744 + 4.0 * 72 * 128 * met))
    assert full.flops_per_sample() == pytest.approx(
        8192 * (2.0 * 44_187_648 + 4.0 * 48 * 128 * 8193 / 2))


@pytest.mark.parametrize("key,value", [
    ("moe_router_logit_softcapping", 30.0),
    ("moe_apply_router_weight_on_input", True), ("norm_topk_prob", False),
    ("attention_bias", True), ("gating", "per-token"),
    ("tie_word_embeddings", True), ("decoder_sparse_step", 2),
    ("gating_types", ["per_head", "elementwise"] * 24),
    ("mlp_only_layers", [0, 1]), ("experts_held", [0, 16])])
def test_from_config_refuses_what_the_class_does_not_build(key, value):
    from flexflow_tpu.models.laguna import LagunaConfig

    LagunaConfig.from_config(_config())                 # the file passes
    with pytest.raises(ValueError, match=key):
        LagunaConfig.from_config(_config(**{key: value}))


def test_apps_lm_trains_the_model_from_its_configuration_file():
    from flexflow_tpu.apps import lm

    lines = []
    out = lm.main(["--model-config", CONFIG, "--preset", "rehearsal",
                   "-b", "2", "-s", "32", "-i", "12", "--seed", "5"],
                  log=lines.append)
    assert any("2 of window 8, 1 full" in l and "[4, 8) of 16 held" in l
               for l in lines[:2])
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
# what the other cells run is what it was


def test_grouped_query_attention_without_extras_is_granites_operator():
    """No rotary, window or gate: Granite's four matrices from the same
    key, and the output bit for bit whatever the new arguments' defaults
    are called."""
    from flexflow_tpu.ops.attention import (GroupedQueryAttention,
                                            grouped_causal_attention)

    x = Tensor((2, 24, 48), "float32")
    old = GroupedQueryAttention("attn", _pc(3), x, 8, 2, 64, 0.03)
    new = GroupedQueryAttention("attn", _pc(3), x, 8, 2, 64, 0.03,
                                rope=None, window=None, gate=False)
    params = old.init_params(jax.random.PRNGKey(0))
    assert list(params) == ["wq", "wk", "wv", "wo"]
    # glorot uniform from the four sub-keys of the operator's key
    init = jax.nn.initializers.glorot_uniform()
    for key, name in zip(jax.random.split(jax.random.PRNGKey(0), 4),
                         params):
        np.testing.assert_array_equal(
            params[name], init(key, params[name].shape, "float32"))
    data = _rand(1, 2, 24, 48)
    got, _ = new.forward(params, {}, [data], True)
    np.testing.assert_array_equal(got, old.forward(params, {}, [data],
                                                   True)[0])
    q, k, v = (data @ params[w] for w in ("wq", "wk", "wv"))
    np.testing.assert_array_equal(
        got, grouped_causal_attention(q, k, v, 8, 2, 0.03) @ params["wo"])
    assert old.cost_signature() == (8, 2, 64, 0.03)
    assert old.flops_per_sample() == 24 * (
        2.0 * (2 * 48 * 512 + 2 * 48 * 128) + 4.0 * 8 * 64 * 25 / 2)


def test_sigmoid_router_is_moonlights_rule_with_its_state():
    """sigmoid, selection by score + bias, renormalised: output and the
    bias's move bit for bit against the rule written out."""
    op, params, state = _router(8, 2, 6, (2, 4), 2.446, score="sigmoid")
    assert set(state) == {"bias"}
    state = {"bias": _rand(7, 8, scale=0.05)}
    x = _rand(8, 2, 4, 6)
    gates, new_state = op.forward(params, state, [x], True)
    logits = jnp.einsum("bsd,de->bse", x, params["kernel"],
                        precision=jax.lax.Precision.HIGHEST)
    score = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(score + state["bias"], 2)
    mask = jnp.sum(jax.nn.one_hot(chosen, 8, dtype=jnp.float32), axis=-2)
    picked = score * mask
    np.testing.assert_array_equal(
        gates, 2.446 * picked / jnp.sum(picked, axis=-1, keepdims=True))
    load = jnp.sum(mask, axis=(0, 1))
    np.testing.assert_array_equal(
        new_state["bias"],
        state["bias"] + 1e-3 * jnp.sign(jnp.mean(load) - load))


# the kernels' programs (jaxpr, forward and backward) of a call without a
# window at the three token cells' shapes, as the parent commit traced
# them (computed on PR 33's tree and on this one: the same); Moonlight's
# and Granite's backward is the fused kernel since the VMEM count picks
# the form (their split programs still trace to c63a... and 2fd5...)
_UNCHANGED = {
    "gpt2_small": ((16, 1024, 12, 64, 64, None), "6d623ac2a3b312cd"),
    "moonlight_16b_a3b": ((2, 8192, 16, 192, 128, None), "c658a335af0ce128"),
    "granite_4_0_h_micro": ((2, 8192, 32, 64, 64, 0.015625),
                            "5766753e83688f39"),
}


@pytest.mark.parametrize("cell", sorted(_UNCHANGED))
def test_flash_without_a_window_traces_to_the_parents_program(cell):
    fa = _flash()
    (b, s, h, d, dv, scale), digest = _UNCHANGED[cell]
    q = jax.ShapeDtypeStruct((b, s, h * d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, s, h * dv), jnp.bfloat16)

    def step(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: fa.flash_attention_packed(
                q, k, v, h, True, interpret=False, scale=scale,
                window=None).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)

    text = str(jax.make_jaxpr(step)(q, q, v))
    assert "ff_flash_fwd" in text and "ff_flash_win" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
