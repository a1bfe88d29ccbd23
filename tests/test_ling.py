"""What Ling-3.0-flash forced: Kimi Delta Attention in its chunked form,
group-limited routing, latent attention with head norms and a head gate,
and the ``bailing_hybrid`` model class ``apps/lm.py`` trains: each against
plain ``jax.numpy``, the whole model against
``benchmarks/reference/ling_3_0_flash.py``, and DeepSeek-V3's groups on
the latent-MoE path."""

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
CONFIG = os.path.join(ROOT, "benchmarks", "configs", "ling_3_0_flash.json")


def _pc(rank):
    return ParallelConfig((1,) * rank, (0,))


def _rand(seed, *shape, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


def _reference():
    from benchmarks.reference import ling_3_0_flash

    return ling_3_0_flash


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
# the chunked delta rule against the recurrence


def _kda_inputs(seed, b, s, h, dk, dv, bound=None):
    from flexflow_tpu.ops.kda import l2_normalize

    rng = np.random.RandomState(seed)
    unit = lambda: l2_normalize(jnp.asarray(rng.randn(b, s, h, dk),
                                            jnp.float32))
    q, k = unit() * dk ** -0.5, unit()
    v = jnp.asarray(rng.randn(b, s, h, dv), jnp.float32)
    if bound is None:
        g = -5.0 * jax.nn.sigmoid(jnp.asarray(rng.randn(b, s, h, dk),
                                              jnp.float32))
    else:   # the published gate far past its knee: the bound itself
        g = bound * jax.nn.sigmoid(jnp.full((b, s, h, dk), 40.0))
    beta = jax.nn.sigmoid(jnp.asarray(rng.randn(b, s, h), jnp.float32))
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta):
    return jax.vmap(_reference().kda_recurrence)(q, k, v, g, beta)


def _against_the_recurrence(inputs, **cut):
    """The chunked form's output and the gradients of a fixed function of
    it by all five inputs, against the recurrence's, both compiled."""
    from flexflow_tpu.ops.kda import kda_chunked

    chunked = lambda *a: kda_chunked(*a, **cut)
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)) * 1.3)
    with jax.default_matmul_precision("highest"):
        for f in (chunked, _recurrence):
            out = jax.jit(f)(*inputs)
            grads = jax.jit(jax.grad(loss(f), argnums=range(5)))(*inputs)
            yield out, grads


@pytest.mark.parametrize("s,cut", [
    (48, dict(chunk=16, sub=4, span=16)),      # three spans of one chunk
    (64, dict(chunk=16, sub=8, span=32)),      # two spans of two chunks
    (40, dict(chunk=64, sub=16)),              # one short chunk
], ids=["spans_of_one", "spans_of_two", "one_chunk"])
def test_chunked_kda_against_the_recurrence_forward_and_every_gradient(s,
                                                                       cut):
    (got, got_g), (want, want_g) = _against_the_recurrence(
        _kda_inputs(1, 2, s, 3, 8, 6), **cut)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for name, a, w in zip(("q", "k", "v", "g", "beta"), got_g, want_g):
        np.testing.assert_allclose(a, w, rtol=1e-4, atol=1e-5 * float(
            jnp.max(jnp.abs(w))), err_msg=name)


def test_a_sequence_the_chunk_does_not_divide():
    """37 steps in chunks of 16: the last chunk is padded with steps that
    change nothing, so the output is the recurrence's and what follows
    step 37 in a longer sequence does not reach it."""
    from flexflow_tpu.ops.kda import kda_chunked

    inputs = _kda_inputs(2, 1, 48, 2, 8, 8)
    short = tuple(a[:, :37] for a in inputs)
    (got, got_g), (want, want_g) = _against_the_recurrence(
        short, chunk=16, sub=8)
    assert got.shape == (1, 37, 2, 8)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for a, w in zip(got_g, want_g):
        np.testing.assert_allclose(a, w, rtol=1e-4, atol=1e-5 * float(
            jnp.max(jnp.abs(w))))
    longer = jax.jit(lambda *a: kda_chunked(*a, chunk=16, sub=8))(*inputs)
    np.testing.assert_allclose(longer[:, :37], got, rtol=1e-6, atol=1e-7)


def test_decays_at_the_bound_across_a_whole_chunk():
    """Every channel's log-decay at -5 for a whole chunk of 64: the
    factors of each sub-chunk stay in range and the chunked form is the
    recurrence's, forward and backward, where one factor about the
    chunk's start overflows (-5 x 64 = -320)."""
    inputs = _kda_inputs(3, 1, 64, 2, 16, 8, bound=-5.0)
    assert float(jnp.min(inputs[3])) == -5.0
    (got, got_g), (want, want_g) = _against_the_recurrence(
        inputs, chunk=64, sub=16)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the factors span exp(+-40): a few bits of the largest gradient
    for name, a, w in zip(("q", "k", "v", "g", "beta"), got_g, want_g):
        assert bool(jnp.all(jnp.isfinite(a))), name
        np.testing.assert_allclose(a, w, rtol=1e-4, atol=1e-4 * float(
            jnp.max(jnp.abs(w))), err_msg=name)
    # the naive factorisation of the same chunk's k k^T decays
    _, k, _, g, _ = inputs
    cum = jnp.cumsum(g[0, :, 0], axis=0)                  # (64, K)
    naive = (k[0, :, 0] * jnp.exp(cum)) @ (k[0, :, 0] * jnp.exp(-cum)).T
    assert not bool(jnp.all(jnp.isfinite(naive)))


def test_one_key_repeated_across_a_chunk():
    """Every position writes the same key with decays near 1, so the
    chunk's triangular system is as far from the identity as it gets
    (each row's entries near beta): the blocked forward substitution
    stays the recurrence's."""
    q, k, v, g, beta = _kda_inputs(5, 1, 64, 2, 8, 8)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    g = g * 1e-3
    (got, got_g), (want, want_g) = _against_the_recurrence(
        (q, k, v, g, beta), chunk=64, sub=16)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for name, a, w in zip(("q", "k", "v", "g", "beta"), got_g, want_g):
        np.testing.assert_allclose(a, w, rtol=1e-4, atol=1e-4 * float(
            jnp.max(jnp.abs(w))), err_msg=name)


def test_the_scan_operator_counts_its_form_and_reads_its_chunk():
    from flexflow_tpu.ops.kda import KDAScan

    t = Tensor((2, 100, 4 * 8))
    op = KDAScan("s", _pc(2), t, t, t, Tensor((2, 100, 32), "float32"),
                 Tensor((2, 100, 4), "float32"), 4, 8, -5.0)
    assert op.chunk == 64 and op.param_bytes() == 0
    assert KDAScan("s", _pc(2), *(Tensor((1, 20, 32)),) * 3,
                   Tensor((1, 20, 32), "float32"),
                   Tensor((1, 20, 4), "float32"), 4, 8, -5.0).chunk == 32
    before = _counted("kernels.kda.xla.4x8c64")
    inputs = _kda_inputs(4, 2, 100, 4, 8, 8)
    flat = [a.reshape(2, 100, -1) for a in inputs[:4]] + [inputs[4]]
    flat[0] = flat[0] * 8 ** 0.5            # the operator scales q itself
    o, _ = jax.jit(lambda *a: op.forward({}, {}, list(a), True))(*flat)
    assert _counted("kernels.kda.xla.4x8c64") == before + 1
    assert _counted("kda.chunks_per_sequence") == 2
    assert _counted("kda.gate_lower_bound") == -5.0
    with jax.default_matmul_precision("highest"):
        want = jax.jit(_recurrence)(*inputs)
    np.testing.assert_allclose(o.reshape(want.shape), want, rtol=1e-5,
                               atol=1e-6)
    with pytest.raises(ValueError, match="grid"):
        KDAScan("s", ParallelConfig((2, 1), (0, 1)), t, t, t,
                Tensor((2, 100, 32), "float32"),
                Tensor((2, 100, 4), "float32"), 4, 8,
                -5.0).validate_partitioning()


# ---------------------------------------------------------------------------
# group-limited routing


def _plain_group_top_k(score, bias, groups, keep, top_k, scale):
    """Each token: groups ranked by their two best ``score + bias``, the
    ``keep`` best kept, the ``top_k`` best experts among theirs."""
    ranked = np.asarray(score + bias, np.float64)
    out = np.zeros_like(ranked)
    per = ranked.shape[-1] // groups
    for t, row in enumerate(ranked):
        best = [np.sort(row[j * per:(j + 1) * per])[-2:].sum()
                for j in range(groups)]
        kept = np.argsort(best)[::-1][:keep]
        masked = np.full_like(row, -np.inf)
        for j in kept:
            masked[j * per:(j + 1) * per] = row[j * per:(j + 1) * per]
        chosen = np.argsort(masked)[::-1][:top_k]
        out[t, chosen] = score[t, chosen]
    return scale * out / out.sum(axis=-1, keepdims=True)


def test_group_limited_routing_against_a_plain_top_k_over_the_kept_groups():
    from flexflow_tpu.ops.expert_share import TopKRouter

    op = TopKRouter("r", _pc(2), Tensor((2, 24, 16)), 32, 5, 2.5,
                    score="sigmoid", n_group=8, topk_group=3)
    params = op.init_params(jax.random.PRNGKey(1))
    bias = _rand(2, 32, scale=0.3)
    x = _rand(3, 2, 24, 16)
    before = _counted("moe.router_groups.3of8")
    gates, st = jax.jit(lambda x, b: op.forward(params, {"bias": b}, [x],
                                                True))(x, bias)
    assert _counted("moe.router_groups.3of8") == before + 1
    with jax.default_matmul_precision("highest"):
        score = jax.nn.sigmoid(x.reshape(48, 16) @ params["kernel"])
    want = _plain_group_top_k(np.asarray(score), np.asarray(bias), 8, 3, 5,
                              2.5)
    np.testing.assert_allclose(gates.reshape(48, 32), want, rtol=1e-5,
                               atol=1e-7)
    # no token took an expert outside its three groups
    used = np.asarray(gates.reshape(48, 8, 4) > 0).any(axis=-1)
    assert used.sum(axis=-1).max() <= 3
    # the limit changes the choice: without groups it is another one
    free = TopKRouter("r", _pc(2), Tensor((2, 24, 16)), 32, 5, 2.5,
                      score="sigmoid")
    ungrouped, _ = jax.jit(lambda x, b: free.forward(
        params, {"bias": b}, [x], True))(x, bias)
    assert bool(jnp.any((ungrouped > 0) != (gates > 0)))
    assert set(st) == {"bias"}


@pytest.mark.parametrize("n_group,topk_group,top_k", [
    (3, 1, 2), (4, 5, 2), (4, 1, 5), (16, 2, 2)])
def test_groups_that_cannot_hold_the_choice_are_refused(n_group, topk_group,
                                                        top_k):
    from flexflow_tpu.ops.expert_share import TopKRouter

    with pytest.raises(ValueError, match="groups"):
        TopKRouter("r", _pc(2), Tensor((2, 8, 16)), 16, top_k, 1.0,
                   n_group=n_group, topk_group=topk_group)


def test_one_group_is_the_ungrouped_program():
    """At n_group 1 the router traces to the equations it had without
    the option: one top_k, no group mask."""
    from flexflow_tpu.ops.expert_share import TopKRouter

    def jaxpr(**groups):
        op = TopKRouter("r", _pc(2), Tensor((2, 8, 16)), 16, 4, 1.0,
                        **groups)
        params = op.init_params(jax.random.PRNGKey(0))
        return str(jax.make_jaxpr(lambda x: op.forward(
            params, op.init_state(), [x], True))(_rand(0, 2, 8, 16)))

    plain = jaxpr()
    assert plain == jaxpr(n_group=1, topk_group=1)
    assert plain.count("top_k") == 1
    assert jaxpr(n_group=4, topk_group=2).count("top_k") == 3


# ---------------------------------------------------------------------------
# latent attention's two options


def _mla(**options):
    from flexflow_tpu.ops.latent_attention import LatentAttention

    return LatentAttention("a", _pc(3), Tensor((2, 12, 32)), 4, 16, 8, 8, 8,
                           6e6, 1e-6, **options)


def test_latent_attention_takes_head_norms_and_a_head_gate():
    plain, gated = _mla(), _mla(qk_norm=1e-6, head_gate=True)
    p = gated.init_params(jax.random.PRNGKey(2))
    assert set(p) - set(plain.init_params(jax.random.PRNGKey(2))) == {
        "q_norm", "k_norm", "wg"}
    assert p["q_norm"].shape == p["k_norm"].shape == (16,)
    assert p["wg"].shape == (32, 4)
    assert gated.param_bytes() == plain.param_bytes() + 4 * (2 * 16
                                                             + 32 * 4)
    x = _rand(5, 2, 12, 32)
    run = jax.jit(lambda op, p: op.forward(p, {}, [x], True)[0],
                  static_argnums=0)
    names = ("attn.qk_norm", "attn.head_gate", "attn.rope.interleave")
    before = {k: _counted(k) for k in names}
    y = run(gated, p)
    assert {k: _counted(k) - v for k, v in before.items()} == dict.fromkeys(
        names, 1)
    # the norms make q's and k's scale irrelevant: every head's k is its
    # own k_nope (wkvb's first 8 columns of each 16) and the shared k_pe
    # (wkva's last 8) ...
    nope = (np.arange(4 * 16) % 16) < 8
    scaled = dict(p, wq=3.0 * p["wq"],
                  wkvb=p["wkvb"] * np.where(nope, 2.0, 1.0),
                  wkva=p["wkva"].at[:, 16:].mul(2.0))
    np.testing.assert_allclose(run(gated, scaled), y, rtol=1e-4, atol=1e-5)
    # ... the gate at zero weights halves every head's result
    shut = dict(p, wg=jnp.zeros_like(p["wg"]), q_norm=p["q_norm"] * 1.0)
    normed_only = _mla(qk_norm=1e-6)
    p_norm = {k: v for k, v in p.items() if k != "wg"}
    np.testing.assert_allclose(run(gated, shut),
                               0.5 * run(normed_only, p_norm), rtol=1e-5,
                               atol=1e-6)
    assert gated.cost_signature() != plain.cost_signature()


# ---------------------------------------------------------------------------
# the model class


@pytest.fixture(scope="module")
def tiny_model():
    from flexflow_tpu.models.ling_hybrid import LingHybridConfig, LingHybridLM

    return LingHybridLM(LingHybridConfig.from_config(
        _tiny_config(), batch_size=2, seq_length=20),
        MachineModel(jax.devices()[:1]))


def test_model_class_builds_the_named_operators(tiny_model):
    names = [op.name for op in tiny_model.layers]
    kda = ["norm1", "kda_in", "kda_scan", "kda_out", "res1", "norm2"]
    moe = ["moe_router", "moe_experts", "moe_shared", "moe_sum", "res2"]
    assert names == ["embed"] + [f"blk0_{n}" for n in kda + ["ffn", "res2"]] \
        + [f"blk1_{n}" for n in kda + moe] \
        + [f"blk2_{n}" for n in ["norm1", "mla", "res1", "norm2"] + moe] \
        + ["final_norm", "lm_head", "softmax"]
    by_name = {op.name: op for op in tiny_model.layers}
    mla = by_name["blk2_mla"]
    assert (mla.qk_norm, mla.head_gate, mla.qk_dim, mla.v_dim) \
        == (1e-6, True, 24, 16)
    router = by_name["blk1_moe_router"]
    assert (router.score, router.n_router, router.top_k, router.scale,
            router.n_group, router.topk_group) \
        == ("sigmoid", 16, 4, 2.5, 4, 2)
    assert by_name["blk1_moe_experts"].experts_held == (4, 8)
    assert by_name["blk1_moe_shared"].d_ff == 32
    scan = by_name["blk0_kda_scan"]
    assert (scan.num_heads, scan.head_dim, scan.chunk, scan.lower_bound) \
        == (4, 16, 32, -5.0)
    assert by_name["blk0_kda_in"].taps == 4
    assert len(tiny_model.recompute_blocks) == 3
    # the published deployment: five KDA layers, then MLA; two dense
    from flexflow_tpu.models.ling_hybrid import LingHybridConfig

    t = LingHybridConfig.from_config(_config())
    assert [t.is_mla(i) for i in range(42)].count(True) == 7
    assert [t.is_mla(i) for i in range(6)] == [False] * 5 + [True]
    assert (t.num_layers, t.first_k_dense_replace, t.experts_held,
            t.router_outputs, t.n_group, t.topk_group) \
        == (6, 2, (0, 8), 512, 8, 4)


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight shares of 2 of 16 experts, top 4 within 2 of 4 groups: the
    router and the shared expert computed once, as every chip computes
    them alike, and the eight held parts added give the uncut reference
    layer."""
    from flexflow_tpu.ops.expert_share import HeldExperts, TopKRouter
    from flexflow_tpu.ops.seq_gated import GatedFFNSeq, RMSNormSeq

    ref = _reference()
    c = _tiny_config(experts_held=[0, 16], num_experts=16)
    d, f, tokens, layer = 64, 32, (2, 24), 1
    x = _rand(14, *tokens, d)
    t = Tensor(tokens + (d,))
    norm2 = RMSNormSeq("n2", _pc(2), t, 1e-6)
    router = TopKRouter("r", _pc(2), t, 16, 4, 2.5, n_group=4, topk_group=2)
    shared = GatedFFNSeq("s", _pc(2), t, 32)

    def share(lo, held=2):
        return HeldExperts("e", _pc(2), t, Tensor(tokens + (16,)), f,
                           (lo, lo + held), 4)

    p = {f"blk{layer}_norm2": {"scale": 1 + 0.2 * _rand(2, d)},
         f"blk{layer}_moe_router": router.init_params(jax.random.PRNGKey(3)),
         f"blk{layer}_moe_shared": shared.init_params(jax.random.PRNGKey(4)),
         f"blk{layer}_moe_experts": share(0, 16).init_params(
             jax.random.PRNGKey(5))}

    @jax.jit
    def shares(m):
        n, _ = norm2.forward(p[f"blk{layer}_norm2"], {}, [m], True)
        gates, _ = router.forward(p[f"blk{layer}_moe_router"],
                                  router.init_state(), [n], True)
        total = m + shared.forward(p[f"blk{layer}_moe_shared"], {}, [n],
                                   True)[0]
        held = []
        for lo in range(0, 16, 2):
            op = share(lo)
            ps = {k: v[lo:lo + 2]
                  for k, v in p[f"blk{layer}_moe_experts"].items()}
            held.append(op.forward(ps, op.init_state(), [n, gates], True))
        return total, held

    total, held = shares(x)
    pairs = 0
    for lo, (y, st) in zip(range(0, 16, 2), held):
        assert float(st["dropped"]) == 0
        pairs += int(jnp.sum(st["counts"][lo:lo + 2]))
        total = total + y
    assert pairs == 4 * 48, "every selection falls in exactly one share"

    def uncut(m):       # the reference block from its feed-forward on
        n = ref._rms(m, p[f"blk{layer}_norm2"]["scale"], 1e-6)
        w = ref.router_weights(p[f"blk{layer}_moe_router"]["kernel"], n, c)
        return m + ref._glu(n, **p[f"blk{layer}_moe_shared"]) \
            + ref.routed_part(p[f"blk{layer}_moe_experts"], w, n, c)

    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(uncut))(x)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)


def test_loss_and_every_operator_gradient_against_the_reference(tiny_model):
    lm_check.check_against_reference(tiny_model, _tiny_config(),
                                      _reference(), 2, 20)


def test_recomputed_step_trains_and_counts_the_mechanisms(tiny_model):
    model = tiny_model
    toks = jax.random.randint(jax.random.PRNGKey(7), (2, 20), 0, 96)
    step, (p, st), losses = model.make_train_step(), model.init(3), []
    for _ in range(8):
        p, st, _, loss = step(p, st, None, toks, toks)
        losses.append(float(loss))
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert float(st["blk1_moe_experts"]["dropped"]) == 0
    assert float(jnp.max(jnp.abs(st["blk1_moe_router"]["bias"]))) > 0
    assert _counted("kda.chunk") == 32
    for name in ("kernels.kda.xla.4x16c32", "moe.router_groups.2of4",
                 "attn.qk_norm", "attn.head_gate", "attn.rope.interleave",
                 "runtime.recomputed_blocks"):
        assert _counted(name) >= 1, name


def test_parameters_are_the_count_the_issue_reckons():
    """759.8 M parameters at the cell's size, from the operators' own
    ``param_bytes`` (no array is made)."""
    from flexflow_tpu.models.ling_hybrid import LingHybridConfig, LingHybridLM

    ff = LingHybridLM(LingHybridConfig.from_config(
        _config(), batch_size=1, seq_length=16384),
        MachineModel(jax.devices()[:1]))
    by_op = {op.name: op.param_bytes() // 4 for op in ff.layers}
    d, di = 2560, 32 * 128
    kda_in = d * (5 * di + 32) + 3 * di * 4 + 32 + di
    kda_out = 128 + di * d
    assert kda_in + kda_out == 63_049_888
    assert by_op["blk0_kda_in"] + by_op["blk0_kda_scan"] \
        + by_op["blk4_kda_out"] == kda_in + kda_out
    dense = 3 * d * 6144
    assert by_op["blk1_ffn"] == dense == 47_185_920
    mla = d * 32 * 192 + d * 576 + 512 * 32 * 256 + 32 * 128 * d \
        + d * 32 + 512 + 2 * 192
    assert by_op["blk5_mla"] == mla == 31_966_080
    expert = 3 * d * 768
    assert by_op["blk2_moe_experts"] == 8 * expert
    assert by_op["blk3_moe_shared"] == expert == 5_898_240
    assert by_op["blk4_moe_router"] == d * 512 == 1_310_720
    assert by_op["embed"] == by_op["lm_head"] == 19648 * d == 50_298_880
    moe = d * 512 + 9 * expert
    norms = 2 * d
    total = 2 * (kda_in + kda_out + dense + norms) \
        + 3 * (kda_in + kda_out + moe + norms) + mla + moe + norms \
        + 2 * 19648 * d + d
    assert sum(by_op.values()) == total == 759_797_920
    experts = next(op for op in ff.layers if op.name == "blk2_moe_experts")
    assert experts.rows_capacity == 4096


@pytest.mark.parametrize("key,value", [
    ("expert_swiglu_limit_list", [0, 0, 0, 0, 7] + [0] * 37),
    ("share_expert_swiglu_limit_list", [0, 0, 5] + [0] * 39),
    ("kda_safe_gate", False), ("use_qk_norm", False),
    ("rope_interleave", False), ("mtp_loss_scaling_factor", 0.3),
    ("tie_word_embeddings", True), ("scoring_func", "softmax"),
    ("gated_attention_proj_granularity_type", "element_wise")])
def test_from_config_refuses_what_the_class_does_not_build(key, value):
    from flexflow_tpu.models.ling_hybrid import LingHybridConfig

    LingHybridConfig.from_config(_config())             # the file passes
    with pytest.raises(ValueError, match=key):
        LingHybridConfig.from_config(_config(**{key: value}))
    # a clamp beyond the kept layers is no concern of this share
    LingHybridConfig.from_config(_config(
        expert_swiglu_limit_list=[0] * 6 + [4] * 36))
    with pytest.raises(ValueError, match="plain SGD"):
        LingHybridConfig.from_config(_config(optimizer={"kind": "adamw"}))


def test_apps_lm_trains_the_model_from_its_configuration_file():
    from flexflow_tpu.apps import lm

    assert lm.MODEL_TYPES["bailing_hybrid"]
    lines = []
    out = lm.main(["--model-config", CONFIG, "--preset", "rehearsal",
                   "-b", "2", "-s", "32", "-i", "10", "--seed", "5"],
                  log=lines.append)
    assert any("by model_type bailing_hybrid" in l
               and "3 blocks (2 KDA, 1 MLA; 1 dense)" in l
               and "[4, 8) of 16 held, top 4 in 2 of 4 groups" in l
               for l in lines[:2])
    losses = out["loss"]
    assert len(losses) == 10 and np.all(np.isfinite(losses))
    assert abs(losses[0] - np.log(96)) < 0.5 and losses[-1] < losses[0]
    assert _counted("moe.dropped_pairs") == 0


# ---------------------------------------------------------------------------
# DeepSeek-V3's groups on the latent-MoE path


def test_latent_moe_takes_a_deepseek_v3_file_at_eight_groups_and_four():
    """A ``deepseek_v3`` file with DeepSeek-V3's ``n_group`` 8 and
    ``topk_group`` 4 (Moonlight's file, whose router has 64 outputs, with
    those two keys changed) builds and trains on the latent-MoE path, each
    token's experts within four groups of eight."""
    from flexflow_tpu.models.latent_moe import LatentMoEConfig, LatentMoELM

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "moonlight_16b_a3b.json")) as f:
        config = json.load(f)
    config.update(config["rehearsal"])
    config.update(n_group=8, topk_group=4, router_outputs=64,
                  experts_held=[0, 8], n_routed_experts=8)
    t = LatentMoEConfig.from_config(config, batch_size=2, seq_length=16)
    assert (t.n_group, t.topk_group) == (8, 4)
    ff = LatentMoELM(t, MachineModel(jax.devices()[:1]))
    router = next(op for op in ff.layers if op.name.endswith("moe_router"))
    assert (router.n_group, router.topk_group, router.n_router) == (8, 4, 64)
    params, state = ff.init(1)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                              t.vocab_size)
    before = _counted("moe.router_groups.4of8")

    @jax.jit
    def gates(params):
        values, _ = ff.apply(params, state, {ff.tokens.tid: toks,
                                             ff.labels.tid: toks}, False)
        return values[router.output.tid]

    picked = np.asarray(gates(params) > 0).reshape(32, 8, 8)
    assert _counted("moe.router_groups.4of8") > before
    assert (picked.sum(axis=(1, 2)) == t.num_experts_per_tok).all()
    assert picked.any(axis=-1).sum(axis=-1).max() <= 4
    step, losses, opt = ff.make_train_step(), [], None
    for _ in range(3):
        params, state, opt, loss = step(params, state, opt, toks, toks)
        losses.append(float(loss))
    assert np.all(np.isfinite(losses))
