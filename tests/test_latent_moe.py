"""The operators of a DeepSeek-V3-style block (RMSNorm, rotary positions,
gated feed-forward, latent attention, the sigmoid router and a chip's
share of the experts), the flash kernels at a value width of their own,
recomputed blocks, and the model class that ``apps/lm.py`` trains: each
against plain ``jax.numpy``, and the expert layer's shares against the
uncut reference of ``benchmarks/reference/moonlight_16b_a3b.py``."""

import contextlib
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
                      "moonlight_16b_a3b.json")


def _pc(rank):
    return ParallelConfig((1,) * rank, (0,))


def _rand(seed, *shape, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


def _reference():
    from benchmarks.reference import moonlight_16b_a3b

    return moonlight_16b_a3b


# ---------------------------------------------------------------------------
# RMSNorm, RoPE, gated feed-forward


def test_rms_norm_against_jax_numpy():
    from flexflow_tpu.ops.seq_gated import RMSNormSeq

    x = _rand(0, 2, 5, 16)
    op = RMSNormSeq("n", _pc(2), Tensor(x.shape), eps=1e-5)
    p = {"scale": 1.0 + 0.1 * _rand(1, 16)}
    y, _ = op.forward(p, {}, [x], True)
    want = x / jnp.sqrt(jnp.mean(x ** 2, -1, keepdims=True) + 1e-5) \
        * p["scale"]
    np.testing.assert_allclose(y, want, rtol=1e-6, atol=1e-6)
    assert set(op.init_params(jax.random.PRNGKey(0))) == {"scale"}


@pytest.mark.parametrize("pairing", ["split", "adjacent"])
def test_rope_against_complex_rotation(pairing):
    from flexflow_tpu.ops.seq_gated import apply_rope, rope_angles

    x = _rand(2, 1, 6, 3, 8)                       # (B, S, H, dim)
    cos, sin = rope_angles(6, 8, 50000.0)
    got = apply_rope(x, cos, sin, pairing)
    z = (x[..., 0::2] + 1j * x[..., 1::2]) \
        * jnp.exp(1j * jnp.arange(6)[:, None, None]
                  * 50000.0 ** (-jnp.arange(0, 8, 2) / 8))
    if pairing == "split":
        want = jnp.concatenate([z.real, z.imag], -1)
    else:
        want = jnp.stack([z.real, z.imag], -1).reshape(x.shape)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_both_rope_pairings_give_equal_scores():
    from flexflow_tpu.ops.seq_gated import apply_rope, rope_angles

    q, k = _rand(3, 1, 9, 2, 16), _rand(4, 1, 9, 16)
    cos, sin = rope_angles(9, 16, 50000.0)
    scores = [jnp.einsum("bqhd,bkd->bhqk", apply_rope(q, cos, sin, p),
                         apply_rope(k, cos, sin, p))
              for p in ("split", "adjacent")]
    np.testing.assert_allclose(scores[0], scores[1], rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError):
        apply_rope(q, cos, sin, "halves")


def test_gated_ffn_against_jax_numpy():
    from flexflow_tpu.ops.seq_gated import GatedFFNSeq

    x = _rand(5, 2, 4, 8)
    op = GatedFFNSeq("f", _pc(2), Tensor(x.shape), 24)
    p = op.init_params(jax.random.PRNGKey(1))
    assert {k: v.shape for k, v in p.items()} == {
        "w_gate": (8, 24), "w_up": (8, 24), "w_down": (24, 8)}
    y, _ = op.forward(p, {}, [x], True)
    want = (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    assert op.flops_per_sample() == 6.0 * 4 * 8 * 24


# ---------------------------------------------------------------------------
# flash attention at query/key width 192 and value width 128

_FLASH = {
    "f32_fused_packed": ("float32", "packed", False, "pad256v128.fused"),
    "f32_split_bhsd": ("float32", "bhsd", True, "pad256v128.split"),
    "bf16_split_packed": ("bfloat16", "packed", True, "pad256v128.split"),
}


def _dense(q, k, v):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / q.shape[-1] ** 0.5
    s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("case", sorted(_FLASH))
def test_flash_at_a_value_width_of_its_own(case, monkeypatch):
    from flexflow_tpu import obs

    dtype, form, split, variant = _FLASH[case]
    fa = importlib.import_module("flexflow_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_FWD_PIECE", 16)
    monkeypatch.setattr(fa, "_BWD_PIECE", 8)
    if split:
        monkeypatch.setattr(fa, "_FUSED_BWD_BYTES", 0)
    fa._make_flash.cache_clear()
    b, h, s = 1, 2, 40
    q, k = (_rand(i, b, h, s, 192).astype(dtype) for i in (6, 7))
    v = _rand(8, b, h, s, 128).astype(dtype)
    w = _rand(9, b, h, s, 128)

    def pack(x):
        return x.transpose(0, 2, 1, 3).reshape(b, s, -1)

    def flash(q, k, v):
        if form == "packed":
            out = fa.flash_attention_packed(pack(q), pack(k), pack(v), h,
                                            True, block_q=16, block_k=16)
            return out.reshape(b, s, h, 128).transpose(0, 2, 1, 3)
        return fa.flash_attention(q, k, v, True, block_q=16, block_k=16)

    def both(attn):
        return jax.value_and_grad(
            lambda q, k, v: (attn(q, k, v).astype(jnp.float32) * w).sum(),
            argnums=(0, 1, 2))

    name = f"kernels.flash.{variant}"
    before = obs.snapshot()["counters"].get(name, 0)
    out = flash(q, k, v)
    _, grads = both(flash)(q, k, v)
    assert obs.snapshot()["counters"].get(name, 0) == before + 2
    fa._make_flash.cache_clear()
    assert out.shape == (b, h, s, 128) and out.dtype == q.dtype
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    _, want = both(_dense)(*f32)
    tol = 6e-2 if dtype == "bfloat16" else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32), _dense(*f32),
                               rtol=tol, atol=tol)
    for got, ref, x in zip(grads, want, (q, k, v)):
        assert got.shape == x.shape and got.dtype == x.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32), ref,
                                   rtol=tol, atol=tol)


def test_flash_layouts_by_widths():
    fa = importlib.import_module("flexflow_tpu.ops.pallas.flash_attention")
    # GPT-2's shapes take the kernels they took before the value width
    assert fa._layout(12, 64, 64) == (2, 64, 64, "pack2")
    assert fa._layout(8, 128, 128) == (1, 128, 128, "pack1")
    assert fa._layout(2, 80, 80) == (1, 128, 128, "pad128")
    # latent attention: each side rounded to whole lanes by itself
    assert fa._layout(16, 192, 128) == (1, 256, 128, "pad256v128")
    assert fa._layout(4, 128, 256) == (1, 128, 256, "pack128v256")
    assert fa._layout(4, 24, 16) == (1, 128, 128, "pad128vpad128")
    _, gpt2 = fa._make_flash((16, 12, 1024, 64), (16, 12, 1024, 64), 64,
                             "bfloat16", "bfloat16", "bfloat16", True, None,
                             None, False, packed=True)
    _, mla = fa._make_flash((2, 16, 8192, 192), (2, 16, 8192, 192), 128,
                            "bfloat16", "bfloat16", "bfloat16", True, None,
                            None, False, packed=True)
    assert (gpt2, mla) == ("pack2.fused", "pad256v128.fused")


# ---------------------------------------------------------------------------
# latent attention against the reference's


def _tiny_config(**over):
    with open(CONFIG) as f:
        config = json.load(f)
    config.update(config["rehearsal"])
    config.update(over)
    return config


def test_latent_attention_against_the_reference():
    from flexflow_tpu.ops.latent_attention import LatentAttention

    c = _tiny_config()
    x = _rand(10, 2, 12, c["hidden_size"])
    op = LatentAttention("a", _pc(3), Tensor(x.shape),
                         c["num_attention_heads"], c["kv_lora_rank"],
                         c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                         c["v_head_dim"], c["rope_theta"])
    p = op.init_params(jax.random.PRNGKey(2))
    p["kv_norm"] = 1.0 + 0.1 * _rand(11, c["kv_lora_rank"])
    y, _ = op.forward(p, {}, [x], True)
    want = jax.vmap(lambda n: _reference()._mla(p, n, c))(x)
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    assert op.param_bytes() == 4 * sum(v.size for v in p.values())
    with pytest.raises(ValueError, match="not implemented"):
        LatentAttention("a", ParallelConfig((1, 2, 1), (0, 1)),
                        Tensor(x.shape), 4, 32, 16, 8, 16,
                        1e4).validate_partitioning()


# ---------------------------------------------------------------------------
# the router


def _router(n_router=8, top_k=2, rate=1e-3, d=16, tokens=(2, 12)):
    from flexflow_tpu.ops.expert_share import TopKRouter

    op = TopKRouter("r", _pc(2), Tensor(tokens + (d,)), n_router, top_k,
                       2.446, rate)
    return op, op.init_params(jax.random.PRNGKey(3)), op.init_state()


def test_router_weights_against_the_reference():
    op, p, st = _router()
    x = _rand(12, 2, 12, 16)
    gates, new = op.forward(p, st, [x], True)
    c = {"num_experts_per_tok": 2, "routed_scaling_factor": 2.446}
    want = _reference().router_weights(p["kernel"], x.reshape(24, 16), c)
    np.testing.assert_allclose(gates.reshape(24, 8), want, rtol=1e-6,
                               atol=1e-7)
    assert gates.dtype == jnp.float32
    np.testing.assert_array_equal((gates > 0).sum(-1), 2)
    np.testing.assert_allclose(gates.sum(-1), 2.446, rtol=1e-6)


def test_selection_bias_moves_against_the_load_and_takes_no_gradient():
    op, p, st = _router(rate=0.01)
    x = _rand(13, 2, 12, 16)
    gates, new = op.forward(p, st, [x], True)
    load = np.asarray((gates > 0).sum((0, 1)), np.float64)
    want = 0.01 * np.sign(load.mean() - load)
    np.testing.assert_allclose(new["bias"], want, atol=1e-9)
    assert set(np.unique(np.abs(np.asarray(new["bias"])))) <= {
        np.float32(0.0), np.float32(0.01)}
    # a bias that favours experts 0 and 1 selects them, and only selects:
    # the weights are still the scores' own
    st2 = {"bias": jnp.zeros(8).at[:2].set(10.0)}
    g2, _ = op.forward(p, st2, [x], True)
    assert bool(jnp.all(g2[..., :2] > 0)) and bool(jnp.all(g2[..., 2:] == 0))
    s = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, p["kernel"]))[..., :2]
    np.testing.assert_allclose(g2[..., :2],
                               2.446 * s / s.sum(-1, keepdims=True),
                               rtol=1e-5)
    # no gradient reaches the state, and evaluation leaves it alone
    grad = jax.grad(lambda b: op.forward(p, {"bias": b}, [x], True)[0]
                    .sum())(st["bias"])
    np.testing.assert_array_equal(grad, 0.0)
    assert op.forward(p, st, [x], False)[1] is st


# ---------------------------------------------------------------------------
# a chip's share of the experts


def _experts(held, n_router=8, top_k=2, d=16, f=24, tokens=(2, 12),
             factor=2.0):
    from flexflow_tpu.ops.expert_share import HeldExperts

    x_t, g_t = Tensor(tokens + (d,)), Tensor(tokens + (n_router,))
    return HeldExperts("e", _pc(2), x_t, g_t, f, held, top_k, factor)


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of all the shares, plus the shared experts counted
    once, are the uncut reference's layer output."""
    ref = _reference()
    c = _tiny_config(experts_held=[0, 16])
    d, n_router, top_k = c["hidden_size"], c["router_outputs"], \
        c["num_experts_per_tok"]
    f = c["moe_intermediate_size"]
    tokens = (2, 16)
    x = _rand(14, *tokens, d)
    whole = _experts((0, n_router), n_router, top_k, d, f, tokens)
    pw = whole.init_params(jax.random.PRNGKey(4))
    router, pr, st = _router(n_router, top_k, d=d, tokens=tokens)
    router.scale = c["routed_scaling_factor"]
    gates = jax.jit(lambda x: router.forward(pr, st, [x], True)[0])(x)
    shared = {k: _rand(15 + i, *shape, scale=0.1) for i, (k, shape) in
              enumerate([("w_gate", (d, 2 * f)), ("w_up", (d, 2 * f)),
                         ("w_down", (2 * f, d))])}
    flat = x.reshape(-1, d)

    @jax.jit
    def layer(x, gates):
        """The uncut reference, its shared experts, and each of eight
        shares of two beside what the reference gives for the same
        experts_held."""
        shares = []
        for lo in range(0, n_router, 2):
            share = _experts((lo, lo + 2), n_router, top_k, d, f, tokens)
            ps = {k: v[lo:lo + 2] for k, v in pw.items()}
            y, state = share.forward(ps, share.init_state(), [x, gates],
                                     True)
            shares.append((y.reshape(-1, d), state, ref.routed_part(
                ps, gates.reshape(-1, n_router), flat,
                dict(c, experts_held=[lo, lo + 2]))))
        return ref.routed_part(pw, ref.router_weights(pr["kernel"], flat,
                                                      c), flat, c), \
            ref._glu(flat, **shared), shares

    routed, total, shares = layer(x, gates)
    want = total + routed
    for y, state, y_ref in shares:
        assert float(state["dropped"]) == 0
        np.testing.assert_array_equal(state["counts"],
                                      (gates > 0).sum((0, 1)))
        np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-5)
        total = total + y
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


@contextlib.contextmanager
def _gmm_kernels(pallas_kernels, monkeypatch):
    """The kernel gate open and ``ff_gmm``'s row tiles, and the pieces a
    cut tile is walked in, cut to test sizes (a buffer of 48 rows is then
    the kernels'), in interpret mode."""
    gm = importlib.import_module("flexflow_tpu.ops.pallas.grouped_mm")
    monkeypatch.setattr(gm, "_ROW_TILES", (32, 16))
    monkeypatch.setattr(gm, "_SUB_ROWS", 8)
    gm._make_gated_ffn.cache_clear()
    try:
        with pallas_kernels():
            yield
    finally:
        gm._make_gated_ffn.cache_clear()


def _counted(name):
    from flexflow_tpu import obs

    return sum(v for k, v in obs.snapshot()["counters"].items()
               if k.startswith(name))


@pytest.mark.parametrize("products", ["ragged_dot", "ff_gmm"])
def test_share_gradients_against_the_reference(products, pallas_kernels,
                                               monkeypatch):
    """``ff_gmm``: the gate open and widths of whole lanes, so the nine
    grouped products run through the kernels."""
    ref = _reference()
    d, f = (128, 128) if products == "ff_gmm" else (16, 24)
    op = _experts((2, 6), d=d, f=f)
    p = op.init_params(jax.random.PRNGKey(5))
    router, pr, st = _router(d=d)
    x, w = _rand(16, 2, 12, d), _rand(17, 24, d)
    c = {"experts_held": [2, 6]}

    def ours(p, x, kernel):
        gates, _ = router.forward({"kernel": kernel}, st, [x], True)
        y, _ = op.forward(p, op.init_state(), [x, gates], True)
        return (y.reshape(24, d) * w).sum()

    def theirs(p, x, kernel):
        flat = x.reshape(24, d)
        weights = ref.router_weights(
            kernel, flat, {"num_experts_per_tok": 2,
                           "routed_scaling_factor": 2.446})
        return (ref.routed_part(p, weights, flat, c) * w).sum()

    before = _counted("kernels.gmm." + products)
    with (_gmm_kernels(pallas_kernels, monkeypatch)
          if products == "ff_gmm" else contextlib.nullcontext()):
        got = jax.jit(jax.grad(ours, (0, 1, 2)))(p, x, pr["kernel"])
    assert _counted("kernels.gmm." + products) == before + 1
    want = jax.jit(jax.grad(theirs, (0, 1, 2)))(p, x, pr["kernel"])
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_nothing_is_dropped_until_rows_capacity_is_passed():
    """A router skewed onto two held experts: every pair is computed
    while the buffer holds them all, whichever experts they fall on; past
    ``rows_capacity`` the state says how many did not fit."""
    ref = _reference()
    tokens, n_router, top_k = (2, 16), 8, 2
    x = _rand(18, *tokens, 16)
    # every token picks experts 0 and 1: 64 pairs on two of four held
    gates = jnp.zeros(tokens + (n_router,)).at[..., 0].set(1.5) \
        .at[..., 1].set(0.9)
    fits = _experts((0, 4), n_router, top_k, tokens=tokens, factor=2.0)
    assert fits.rows_capacity == 64       # twice the balanced 32
    p = fits.init_params(jax.random.PRNGKey(6))
    y, st = fits.forward(p, fits.init_state(), [x, gates], True)
    assert float(st["dropped"]) == 0
    np.testing.assert_array_equal(st["counts"], [32, 32, 0, 0, 0, 0, 0, 0])
    want = ref.routed_part(p, gates.reshape(-1, n_router),
                           x.reshape(-1, 16), {"experts_held": [0, 4]})
    np.testing.assert_allclose(y.reshape(-1, 16), want, rtol=1e-4,
                               atol=1e-5)
    tight = _experts((0, 4), n_router, top_k, tokens=tokens, factor=1.25)
    assert tight.rows_capacity == 40
    y2, st2 = tight.forward(p, tight.init_state(), [x, gates], True)
    assert float(st2["dropped"]) == 24
    # the buffer fills expert by expert: expert 0 whole, expert 1's first 8
    first = gates.at[..., 1].set(
        jnp.where(jnp.arange(32).reshape(tokens) < 8, 0.9, 0.0))
    want2 = ref.routed_part(p, first.reshape(-1, n_router),
                            x.reshape(-1, 16), {"experts_held": [0, 4]})
    np.testing.assert_allclose(y2.reshape(-1, 16), want2, rtol=1e-4,
                               atol=1e-5)
    assert tight.state_counters(st2) == {
        "moe.load_max_over_mean": (4.0, "max"),
        "moe.dropped_pairs": (24.0, "sum")}


@pytest.mark.parametrize("grid", [(2, 1), (1, 2)])
def test_expert_grids_that_are_not_implemented_are_refused(grid):
    from flexflow_tpu.ops.expert_share import HeldExperts, TopKRouter

    pc = ParallelConfig(grid, (0, 1))
    x, g = Tensor((2, 12, 16)), Tensor((2, 12, 8))
    with pytest.raises(ValueError, match="not implemented"):
        HeldExperts("e", pc, x, g, 24, (0, 4), 2).validate_partitioning()
    with pytest.raises(ValueError, match="not implemented"):
        TopKRouter("r", pc, x, 8, 2, 1.0).validate_partitioning()
    with pytest.raises(ValueError, match="no range"):
        HeldExperts("e", _pc(2), x, g, 24, (4, 12), 2)


# ---------------------------------------------------------------------------
# the model class, recomputation, apps/lm.py


@pytest.fixture(scope="module")
def tiny_model():
    from flexflow_tpu.models.latent_moe import LatentMoEConfig, LatentMoELM

    t = LatentMoEConfig.from_config(_tiny_config(), batch_size=2,
                                    seq_length=16)
    return LatentMoELM(t, MachineModel(jax.devices()[:1]))


def test_model_class_builds_the_named_operators(tiny_model):
    names = [op.name for op in tiny_model.layers]
    for want in ("blk0_mla", "blk0_ffn", "blk1_mla", "blk1_moe_router",
                 "blk1_moe_experts", "blk1_moe_shared", "blk2_moe_experts",
                 "final_norm", "lm_head"):
        assert want in names
    assert "blk0_moe_router" not in names and "blk1_ffn" not in names
    assert [len(r) for r in tiny_model.recompute_blocks] == [6, 9, 9]
    params, state = tiny_model.init(0)
    assert set(params["lm_head"]) == {"kernel"}          # no bias
    assert set(state) == {f"blk{i}_moe_{k}" for i in (1, 2)
                          for k in ("router", "experts")}
    assert params["blk1_moe_experts"]["w_gate"].shape == (4, 64, 32)
    with pytest.raises(ValueError, match="builds 'sigmoid' only"):
        from flexflow_tpu.models.latent_moe import LatentMoEConfig

        LatentMoEConfig.from_config(_tiny_config(scoring_func="softmax"))


@pytest.mark.parametrize("products", ["ragged_dot", "ff_gmm"])
def test_recomputed_step_equals_the_plain_step(products, tiny_model,
                                               pallas_kernels, monkeypatch):
    """Recomputation changes where values are kept, not what is
    computed: the step's loss, state and updated weights are those of the
    same graph run without it.  ``ff_gmm``: the same at widths of whole
    lanes with the kernel gate open, where the flash kernels and the
    experts' grouped products run as Pallas calls inside the recomputed
    blocks."""
    from flexflow_tpu.models.latent_moe import LatentMoEConfig, LatentMoELM

    kernels = products == "ff_gmm"
    model = tiny_model
    if kernels:
        model = LatentMoELM(LatentMoEConfig.from_config(
            _tiny_config(hidden_size=128, moe_intermediate_size=128,
                         num_layers=2), batch_size=2, seq_length=16),
            MachineModel(jax.devices()[:1]))
    toks = jax.random.randint(jax.random.PRNGKey(7), (2, 16), 0, 96)
    params, state = model.init(3)
    before = jax.tree.map(np.asarray, params)
    counted = _counted("kernels.gmm." + products)
    blocks = model.recompute_blocks
    with (_gmm_kernels(pallas_kernels, monkeypatch) if kernels
          else contextlib.nullcontext()):
        out = model.make_train_step()(params, state, None, toks, toks)
        assert _counted("kernels.gmm." + products) > counted
        try:
            model.recompute_blocks = ()
            model._recompute_cache = None
            params2, state2 = model.init(3)
            plain = model.make_train_step()(params2, state2, None, toks,
                                            toks)
        finally:
            model.recompute_blocks = blocks
            model._recompute_cache = None
    np.testing.assert_allclose(out[3], plain[3], rtol=1e-6)
    # (two units in the last place of a weight near 1, at the wider model)
    for a, b, p0 in zip(jax.tree.leaves(out[0]), jax.tree.leaves(plain[0]),
                        jax.tree.leaves(before)):
        np.testing.assert_allclose(a - p0, b - p0, rtol=1e-3,
                                   atol=2.5e-7 if kernels else 1e-7)
    for a, b in zip(jax.tree.leaves(out[1]), jax.tree.leaves(plain[1])):
        np.testing.assert_array_equal(a, b)


def test_operator_table_charges_a_recomputed_block_to_backward(tiny_model):
    from flexflow_tpu import obs
    from flexflow_tpu.obs import optrace

    before = obs.snapshot()["counters"].get("runtime.recomputed_blocks", 0)
    toks = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    table = tiny_model.operator_table(toks, toks)      # refuses nothing
    assert obs.snapshot()["counters"]["runtime.recomputed_blocks"] \
        == before + 3
    seen = set(table.values())
    for name in ("blk0_mla", "blk1_moe_experts", "blk2_moe_router",
                 "blk1_moe_shared", "blk0_ffn"):
        assert (name, "forward") in seen and (name, "backward") in seen
    path = "jit(ff_train_step)/transpose(jvp(jvp()))/checkpoint/"
    ops = {"blk1_mla", "blk1_moe_experts"}
    assert optrace.classify(path + "rematted_computation/blk1_mla/mul",
                            ops) == ("blk1_mla", "backward")
    assert optrace.classify(path + "blk1_moe_experts/dot_general", ops) \
        == ("blk1_moe_experts", "backward")
    assert optrace.classify(path + "rematted_computation/blk1_mla/mul") \
        == ("blk1_mla", "backward")
    assert optrace.classify("jit(ff_train_step)/jvp(blk1_mla)/mul", ops) \
        == ("blk1_mla", "forward")


def _kernel_calls(jaxpr):
    """The names of the pallas_calls of a jaxpr, nested jaxprs included
    (not the kernels' own bodies)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"]
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_calls(sub)


def _equations(jaxpr):
    """How often a jaxpr applies each primitive to which shapes, nested
    jaxprs included: what a program computes and keeps, whatever
    sub-jaxprs JAX's caches let it share."""
    import collections

    seen = collections.Counter()
    for eqn in jaxpr.eqns:
        seen[(eqn.primitive.name,
              *(v.aval.str_short() for v in eqn.invars))] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            seen.update(_equations(sub))
    return seen


@pytest.mark.parametrize("attention", ["kernels", "blockwise"])
def test_a_recomputed_block_keeps_what_the_kernels_name(
        tiny_model, attention, monkeypatch, pallas_kernels):
    """With the flash kernels on (interpret mode here, through the gate
    ``flash_enabled`` reads) the differentiated step runs the forward
    kernel once a layer, not twice: each block keeps the kernel's
    ``out`` and ``lse``, and the two counters read what the shapes say.
    On XLA's blockwise path no name is traced, the policy keeps nothing
    and the step is what a bare ``jax.checkpoint`` gives."""
    from flexflow_tpu import obs

    args = (*tiny_model.abstract_train_state(),
            *[jax.ShapeDtypeStruct((2, 16), jnp.int32)] * 2)
    before = obs.snapshot()["counters"]
    with (pallas_kernels() if attention == "kernels"
          else contextlib.nullcontext()):
        traced = tiny_model.make_train_step().trace(*args)
    after = obs.snapshot()["counters"]
    calls = sorted(_kernel_calls(traced.jaxpr.jaxpr))
    kept = after.get("runtime.kept_results", 0) \
        - before.get("runtime.kept_results", 0)
    if attention == "kernels":
        # heads of 24 and 16 each ride 128 lanes; float32 at the tiny
        # preset: out (2, 16, 4 * 128) and lse (2, 4, 1, 16) a layer
        assert calls == ["ff_flash_bwd"] * 3 + ["ff_flash_fwd"] * 3
        assert kept == 6
        assert after["runtime.kept_bytes"] \
            == 3 * 4 * (2 * 16 * 4 * 128 + 2 * 4 * 16)
        return
    assert calls == [] and kept == 0
    assert after["runtime.kept_bytes"] == 0
    bare = jax.checkpoint
    monkeypatch.setattr(jax, "checkpoint", lambda body, policy: bare(body))
    assert _equations(tiny_model.make_train_step().trace(*args).jaxpr.jaxpr) \
        == _equations(traced.jaxpr.jaxpr)


def test_a_model_without_recompute_blocks_lowers_as_before(
        monkeypatch, pallas_kernels):
    """TransformerLM names no block to recompute: its step holds no
    checkpoint, and its plan is empty."""
    from flexflow_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)

    ff = TransformerLM(TransformerConfig(
        batch_size=2, seq_length=16, num_layers=2, d_model=32, num_heads=4,
        d_ff=64, vocab_size=97, causal=True),
        MachineModel(jax.devices()[:1]))
    assert ff._recompute_plan({}) == {}
    params, state, opt = ff.abstract_train_state()
    toks = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    text = ff.make_train_step().lower(params, state, opt, toks,
                                      toks).as_text()
    assert "checkpoint" not in text and "remat" not in text
    assert "optimization_barrier" not in text
    # outside a jax.checkpoint the names the flash kernels give their
    # results are identities: with the kernels on, the step lowers to
    # the text it has without them
    from flexflow_tpu import obs

    fa = importlib.import_module("flexflow_tpu.ops.pallas.flash_attention")

    def lowered():
        # without the counter MLIR's symbol table ends a repeated
        # function name with: it moves with what else was lowered
        with pallas_kernels():
            return re.sub(r"@(\w+?)_\d+\b", r"@\1",
                          ff.make_train_step().lower(
                              params, state, opt, toks, toks).as_text())

    flash = "kernels.flash.pad128.fused"        # 4 heads of 8
    before = obs.snapshot()["counters"].get(flash, 0)
    named = lowered()
    assert obs.snapshot()["counters"][flash] == before + 2
    assert named != text and "checkpoint" not in named
    # run_fwd looks the function up when the op is traced
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    assert lowered() == named


def test_apps_lm_trains_the_model_from_its_configuration_file(tmp_path):
    from flexflow_tpu import obs
    from flexflow_tpu.apps import lm

    lines = []
    out = lm.main(["--model-config", CONFIG, "--preset", "rehearsal",
                   "-b", "2", "-s", "32", "-i", "12", "--seed", "5"],
                  log=lines.append)
    assert any("experts [4, 8) of 16 held" in l for l in lines[:2])
    losses = out["loss"]
    assert len(losses) == 12 and np.all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    counters = obs.snapshot()["counters"]
    assert counters["moe.dropped_pairs"] == 0
    assert counters["moe.load_max_over_mean"] >= 1.0
    assert counters["moe.experts_held"] == 4
    with pytest.raises(SystemExit, match="positions"):
        lm.main(["--model-config", CONFIG, "--preset", "rehearsal",
                 "-s", "64", "-i", "1"], log=lines.append)


def test_a_rewritten_custom_call_takes_its_operands_operator():
    """XLA's TPU backend puts ``ragged-dot-none`` custom calls where
    ``jax.lax.ragged_dot`` stood and drops the scope from their
    ``op_name``: the table charges them to what feeds them."""
    from flexflow_tpu.obs import optrace

    hlo = """HloModule jit_ff_train_step

ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%f, metadata={op_name="jit(ff_train_step)/jvp(blk1_moe_experts)/mul"}
  %fusion.2 = s32[8]{0} fusion(%p0), kind=kLoop, calls=%g, metadata={op_name="jit(ff_train_step)/transpose(jvp(jvp()))/checkpoint/blk1_moe_experts/sub"}
  %ragged-dot-metadata.1 = (s32[9]{0}, s32[1]{0}) custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %get-tuple-element.1 = s32[9]{0} get-tuple-element(%ragged-dot-metadata.1), index=0
  %ragged-dot-none.1 = f32[8]{0} custom-call(%get-tuple-element.1, %fusion.1, %p0), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %other.1 = f32[8]{0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="some-rewrite"}
  ROOT %copy.1 = f32[8]{0} copy(%ragged-dot-none.1)
}
"""
    table = optrace.operator_table(hlo, {"blk1_moe_experts"})
    assert table["ragged-dot-none.1"] == ("blk1_moe_experts", "forward")
    assert table["ragged-dot-metadata.1"] == ("blk1_moe_experts",
                                              "backward")
    assert table["other.1"] == ("", "other")
    assert table["copy.1"] == ("", "other")      # no metadata: as before


def test_trace_instructions_sets_the_recomputed_forward_apart(
        tmp_path, monkeypatch, capsys):
    """``tools/trace_instructions.py <cell> <text> <steps>``: the forward
    a block runs once more (``rematted_computation/``) is a part of its
    own beside the backward proper, a rewritten custom call follows its
    operands there too, and a text of another program is refused."""
    import sys

    monkeypatch.syspath_prepend(os.path.join(ROOT, "tools"))
    monkeypatch.delitem(sys.modules, "trace_instructions", raising=False)
    tool = importlib.import_module("trace_instructions")
    path = "jit(ff_train_step)/transpose(jvp(jvp()))/checkpoint/"
    hlo = tmp_path / "step.txt"
    hlo.write_text(f"""HloModule jit_ff_train_step

ENTRY %main (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8]{{0}} parameter(0)
  %fusion.1 = f32[8]{{0}} fusion(%p0), kind=kLoop, calls=%f, metadata={{op_name="jit(ff_train_step)/jvp(blk1_mla)/mul"}}
  %fusion.2 = f32[8]{{0}} fusion(%p0), kind=kLoop, calls=%g, metadata={{op_name="{path}rematted_computation/blk1_mla/mul"}}
  %ragged-dot-none.1 = f32[8]{{0}} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  %fusion.3 = f32[8]{{0}} fusion(%p0), kind=kLoop, calls=%h, metadata={{op_name="{path}blk1_mla/mul"}}
  ROOT %copy.1 = f32[8]{{0}} copy(%fusion.3)
}}
""")
    monkeypatch.setattr(tool, "ROOT", str(tmp_path))
    os.makedirs(tmp_path / "chiprun_out")
    seconds = {"fusion.1": 0.004, "fusion.2": 0.002,
               "ragged-dot-none.1": 0.001, "fusion.3": 0.006,
               "copy.1": 0.0005}

    def dump(d):
        with open(tmp_path / "chiprun_out" / "instructions.cell.json",
                  "w") as f:
            json.dump(d, f)

    dump(seconds)
    assert tool.by_operator("cell", str(hlo), 2) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["ms_per_step"]["blk1_mla"] == {
        "forward": 2.0, "recomputed": 1.5, "backward": 3.0}
    assert out["ms_per_step"]["(none)"] == {"other": 0.25}
    assert out["sum"] == {"forward": 2.0, "recomputed": 1.5,
                          "backward": 3.0}
    dump(dict(seconds, **{"fusion.9": 0.001}))
    with pytest.raises(SystemExit, match="not the traced program"):
        tool.by_operator("cell", str(hlo), 2)
