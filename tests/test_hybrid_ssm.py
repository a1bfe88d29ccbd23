"""The operators of a hybrid state-space block (the Mamba-2 mixer's three
operators, grouped-query attention without positions, the tied head, the
scaled residual and embedding) and the model class that ``apps/lm.py``
trains from a ``granitemoehybrid`` configuration: each against plain
``jax.numpy``, the chunked scan against the recurrence over time steps,
and the whole model against ``benchmarks/reference/granite_4_0_h_micro.py``."""

import contextlib
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
CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                      "granite_4_0_h_micro.json")


def _pc(rank):
    return ParallelConfig((1,) * rank, (0,))


def _rand(seed, *shape, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


def _reference():
    from benchmarks.reference import granite_4_0_h_micro

    return granite_4_0_h_micro


def _tiny_config(**over):
    with open(CONFIG) as f:
        config = json.load(f)
    config.update(config["rehearsal"])
    config.update(over)
    return config


def _counted(name):
    from flexflow_tpu import obs

    return obs.snapshot()["counters"].get(name, 0)


# ---------------------------------------------------------------------------
# the scan, the convolution


def _recurrence(x, dt, a, b, c, d):
    """y_t = H_t C_t + D x_t with H_t = exp(dt_t A) H_{t-1} + dt_t x_t
    (outer) B_t, one step after another: x (B, S, H, P), dt (B, S, H),
    b and c (B, S, N)."""
    bsz, s, h, p = x.shape
    state = jnp.zeros((bsz, h, p, b.shape[-1]))
    ys = []
    for t in range(s):
        state = jnp.exp(dt[:, t] * a)[:, :, None, None] * state \
            + (dt[:, t, :, None] * x[:, t])[..., None] \
            * b[:, t, None, None, :]
        ys.append(jnp.einsum("bhpn,bn->bhp", state, c[:, t])
                  + d[:, None] * x[:, t])
    return jnp.stack(ys, axis=1)


def _scan_operands(s, seed=0):
    x = _rand(seed, 2, s, 3, 4)
    dt = jax.nn.softplus(_rand(seed + 1, 2, s, 3) - 1.0)
    a = -jnp.exp(_rand(seed + 2, 3, scale=0.5))
    return (x, dt, a, _rand(seed + 3, 2, s, 5), _rand(seed + 4, 2, s, 5),
            _rand(seed + 5, 3))


@pytest.mark.parametrize("chunk", [1, 3, 10, 64])
def test_chunked_scan_equals_the_recurrence_over_time_steps(chunk):
    """Chunks of one step, of three (which does not divide the ten steps),
    of the whole sequence and beyond it: values and all six gradients."""
    from flexflow_tpu.ops.ssm import ssd_chunked

    ops = _scan_operands(10)
    weight = _rand(9, 2, 10, 3, 4)

    def loss(fn, *args):
        return jnp.sum(fn(*args) * weight)

    chunked = lambda *args: ssd_chunked(*args, chunk)
    np.testing.assert_allclose(jax.jit(chunked)(*ops),
                               jax.jit(_recurrence)(*ops),
                               rtol=2e-5, atol=2e-6)
    got = jax.jit(jax.grad(lambda *a: loss(chunked, *a),
                           argnums=range(6)))(*ops)
    want = jax.jit(jax.grad(lambda *a: loss(_recurrence, *a),
                            argnums=range(6)))(*ops)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_chunked_scan_survives_decays_that_underflow(path):
    """exp of a positive difference above the diagonal is never formed:
    steps of delta A = -60 leave finite values and gradients, in
    ``ssd_chunked`` and in the Pallas kernels (interpret mode, at shapes
    that are theirs: 8 heads of 16, state 128, chunks of 128)."""
    from flexflow_tpu.ops.pallas.ssd_scan import ssd_scan
    from flexflow_tpu.ops.ssm import ssd_chunked

    if path == "xla":
        x, dt, a, b, c, d = _scan_operands(8)
        scan = lambda x, dt: ssd_chunked(x, dt, a, b, c, d, 4)
    else:
        x, dt, a, d = (_rand(0, 1, 256, 8, 16), _rand(1, 1, 256, 8),
                       _rand(2, 8), _rand(5, 8))
        b, c = _rand(3, 1, 256, 128), _rand(4, 1, 256, 128)
        scan = lambda x, dt: ssd_scan(
            jnp.concatenate([x.reshape(1, 256, 128), b, c], -1), dt, a, d,
            heads=8, head_dim=16, state=128, chunk=128,
            interpret=True).reshape(x.shape)
    dt, a = dt * 0 + 3.0, a * 0 - 20.0
    f = lambda x, dt: jnp.sum(scan(x, dt) ** 2)
    value, grads = jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(x, dt)
    assert np.isfinite(value) and all(np.all(np.isfinite(g)) for g in grads)
    scanned = jax.jit(scan)(x, dt)
    if path == "xla":
        np.testing.assert_allclose(scanned,
                                   jax.jit(_recurrence)(x, dt, a, b, c, d),
                                   rtol=1e-5, atol=1e-6)
        return
    with jax.default_matmul_precision("highest"):
        want = _recurrence(x, dt, a, b, c, d)
        # (the kernels' sums run over a state of 128 numbers and values
        # of tens, where the case above has 5: the same limit in units
        # of the largest value)
        np.testing.assert_allclose(
            scanned, want, rtol=1e-5,
            atol=1e-6 * max(1.0, float(jnp.max(jnp.abs(want)))))


def test_causal_convolution_against_a_loop():
    from flexflow_tpu.ops.ssm import causal_conv1d

    x, w, b = _rand(0, 2, 7, 5), _rand(1, 5, 4), _rand(2, 5)
    want = np.zeros((2, 7, 5), np.float32)
    for t in range(7):
        for ch in range(5):
            acc = np.float32(b[ch])
            for j in range(4):
                if t - 3 + j >= 0:
                    acc += w[ch, j] * x[:, t - 3 + j, ch]
            want[:, t, ch] = acc
    np.testing.assert_allclose(causal_conv1d(x, w, b), want, rtol=1e-5,
                               atol=1e-6)
    # nothing of a later step reaches an earlier one
    moved = causal_conv1d(x.at[:, 4].add(1.0), w, b)
    np.testing.assert_array_equal(moved[:, :4], causal_conv1d(x, w, b)[:, :4])


def test_mixer_operators_against_the_reference():
    """SSMIn, SSMScan and SSMOut chained on one input against the
    reference's mixer (the recurrence), with a chunk that does not divide
    the sequence."""
    from flexflow_tpu.ops.ssm import SSMIn, SSMOut, SSMScan

    cfg = _tiny_config()
    h, hd, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    u = Tensor((2, 20, 64), "float32")
    first = SSMIn("in", _pc(2), u, h, hd, n, cfg["mamba_d_conv"])
    scan = SSMScan("scan", _pc(2), first.xbc, first.delta, h, hd, n, 8)
    out = SSMOut("out", _pc(2), scan.output, first.z, 64)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    p_in, p_scan, p_out = (op.init_params(k) for op, k in
                           zip((first, scan, out), keys))
    p_scan["D"] = _rand(5, h)
    x = _rand(1, 2, 20, 64)

    @jax.jit
    def chained(x):
        (z, xbc, delta), _ = first.forward(p_in, {}, [x], True)
        y, _ = scan.forward(p_scan, {}, [xbc, delta], True)
        return out.forward(p_out, {}, [y, z], True)[0], delta

    got, delta = chained(x)
    assert delta.dtype == jnp.float32 and delta.shape == (2, 20, h)
    ref = _reference()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(
            lambda r: ref._mamba(p_in, p_scan, p_out, r, cfg)))(x)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # dt_bias gives time steps within 0.001-0.1 at a zero projection
    dt0 = jax.nn.softplus(p_in["dt_bias"])
    assert float(dt0.min()) >= 0.00099 and float(dt0.max()) <= 0.1001
    np.testing.assert_allclose(p_scan["A_log"], np.log(np.arange(1, h + 1)),
                               rtol=1e-6)


@pytest.mark.parametrize("grid", [(2, 1), (1, 2)])
def test_scan_grids_that_are_not_implemented_are_refused(grid):
    from flexflow_tpu.ops.ssm import SSMScan

    op = SSMScan("scan", ParallelConfig(grid, (0, 1)),
                 Tensor((2, 16, 4 * 8 + 2 * 4), "float32"),
                 Tensor((2, 16, 4), "float32"), 4, 8, 4, 8)
    with pytest.raises(ValueError, match=r"grid \(1, 1\) only"):
        op.validate_partitioning()


# ---------------------------------------------------------------------------
# grouped-query attention, the flash kernels' scale


def _attention_by_repeated_heads(q, k, v, h, kv, scale):
    """Every query head with a key-value head of its own: k and v
    repeated, a masked softmax a head."""
    b, s, _ = q.shape
    qh = q.reshape(b, s, h, -1)
    kh, vh = (jnp.repeat(x.reshape(b, s, kv, -1), h // kv, axis=2)
              for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) * scale
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores,
                                 -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vh).reshape(b, s, -1)


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_grouped_query_attention_against_repeated_heads(path,
                                                        pallas_kernels):
    """8 query heads on 2 key-value heads of width 64 (``pack2``), a scale
    that is not 1/sqrt(64): the operator against attention with k and v
    repeated, values and weight gradients; with the kernel gate open the
    scores run in the flash kernels."""
    from flexflow_tpu.ops.attention import GroupedQueryAttention

    h, kv, hd, scale = 8, 2, 64, 0.03
    op = GroupedQueryAttention("attn", _pc(3), Tensor((2, 24, 48), "float32"),
                               h, kv, hd, scale)
    params = op.init_params(jax.random.PRNGKey(0))
    assert set(params) == {"wq", "wk", "wv", "wo"}              # no bias
    assert params["wk"].shape == (48, kv * hd)
    x = _rand(1, 2, 24, 48)

    def plain(p):
        q, k, v = (x @ p[w] for w in ("wq", "wk", "wv"))
        return _attention_by_repeated_heads(q, k, v, h, kv, scale) @ p["wo"]

    flash = "kernels.flash.pack2.fused"
    before = _counted(flash)
    with pallas_kernels() if path == "kernels" else contextlib.nullcontext():
        got, grads = jax.jit(jax.value_and_grad(
            lambda p: jnp.sum(op.forward(p, {}, [x], True)[0] ** 2)))(
                params)
    assert _counted(flash) == before + (path == "kernels")
    assert _counted("attn.kv_groups") == 4
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: jnp.sum(plain(p) ** 2)))(params)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for key in params:
        np.testing.assert_allclose(grads[key], want_grads[key], rtol=2e-3,
                                   atol=2e-5)
    assert op.param_bytes() == 4 * (2 * 48 * 512 + 2 * 48 * 128)
    with pytest.raises(ValueError, match="do not divide"):
        GroupedQueryAttention("a", _pc(3), Tensor((2, 8, 48), "float32"),
                              8, 3, 64, scale)


def test_flash_takes_a_scale_that_is_a_given_number():
    from flexflow_tpu.ops.pallas.flash_attention import \
        flash_attention_packed

    q, k, v = (_rand(i, 1, 16, 2 * 64) for i in range(3))
    got = flash_attention_packed(q, k, v, 2, causal=True, scale=1 / 64)
    want = _attention_by_repeated_heads(q, k, v, 2, 2, 1 / 64)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    default = flash_attention_packed(q, k, v, 2, causal=True)
    np.testing.assert_allclose(
        default, _attention_by_repeated_heads(q, k, v, 2, 2, 0.125),
        rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# the tied head, the multipliers


@pytest.fixture(scope="module")
def tiny_model():
    from flexflow_tpu.models.hybrid_ssm import HybridSSMConfig, HybridSSMLM

    t = HybridSSMConfig.from_config(_tiny_config(), batch_size=2,
                                    seq_length=20)
    return HybridSSMLM(t, MachineModel(jax.devices()[:1]))


def test_model_class_builds_the_named_operators(tiny_model):
    names = [op.name for op in tiny_model.layers]
    for want in ("blk0_ssm_in", "blk0_ssm_scan", "blk0_ssm_out", "blk0_ffn",
                 "blk1_attn", "blk1_ffn", "blk2_ssm_scan", "final_norm",
                 "lm_head"):
        assert want in names
    assert "blk1_ssm_scan" not in names and "blk0_attn" not in names
    assert [len(r) for r in tiny_model.recompute_blocks] == [8, 6, 8]
    assert _counted("ssm.layers") == 2
    from flexflow_tpu.models.hybrid_ssm import HybridSSMConfig

    for key, value, said in (("mamba_n_groups", 2, "builds 1 only"),
                             ("num_local_experts", 4, "builds 0 only"),
                             ("position_embedding_type", "rope",
                              "builds 'nope' only"),
                             ("tie_word_embeddings", False,
                              "builds True only")):
        with pytest.raises(ValueError, match=said):
            HybridSSMConfig.from_config(_tiny_config(**{key: value}))
    with pytest.raises(ValueError, match="layer_types"):
        HybridSSMConfig.from_config(_tiny_config(num_layers=4))


def test_tied_head_is_one_parameter_with_the_gradient_of_both_uses(
        tiny_model):
    ff = tiny_model
    params, state = ff.init(0)
    assert "lm_head" not in params and set(params["embed"]) == {"table"}
    head = ff.layers[[op.name for op in ff.layers].index("lm_head")]
    assert head.param_key == "embed" and head.param_bytes() == 0
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 20), 0, 96)
    cfg = _tiny_config()

    grad = jax.jit(jax.grad(
        lambda p: ff.loss_fn(p, state, toks, toks)[0]))(params)

    # the same loss with the two uses under two names
    ref = _reference()
    plain = jax.tree.map(jnp.asarray, dict(params))

    def two_tables(lookup, head_table):
        p = dict(plain, embed={"table": lookup})
        x = ref.hidden(p, toks, cfg)
        targets = jnp.concatenate([toks[:, 1:], toks[:, :1]], axis=1)
        counted = (jnp.arange(20) < 19).astype(jnp.float32)
        return ref._nll_sum(head_table, x.reshape(40, -1),
                            targets.reshape(-1), jnp.tile(counted, 2),
                            float(cfg["logits_scaling"])) / (2 * 19)

    with jax.default_matmul_precision("highest"):
        table = plain["embed"]["table"]
        by_lookup, by_head = jax.jit(jax.grad(two_tables, argnums=(0, 1)))(
            table, table)
    assert float(jnp.linalg.norm(by_lookup)) > 0
    assert float(jnp.linalg.norm(by_head)) > 0
    np.testing.assert_allclose(grad["embed"]["table"], by_lookup + by_head,
                               rtol=2e-3, atol=1e-6)


def test_multipliers_scale_the_embedding_and_the_branches():
    from flexflow_tpu.ops.embed import Embed
    from flexflow_tpu.ops.seq_common import AddSeq

    ids = Tensor((2, 5), "int32")
    plain = Embed("e", _pc(1), ids, 11, 4)
    scaled = Embed("e", _pc(1), ids, 11, 4, multiplier=12.0)
    p = plain.init_params(jax.random.PRNGKey(0))
    tok = jnp.arange(10).reshape(2, 5)
    np.testing.assert_allclose(scaled.forward(p, {}, [tok], True)[0],
                               12.0 * plain.forward(p, {}, [tok], True)[0],
                               rtol=1e-6)
    x, y = _rand(0, 2, 5, 4), _rand(1, 2, 5, 4)
    t = Tensor((2, 5, 4), "float32")
    np.testing.assert_allclose(
        AddSeq("r", _pc(2), [t, t], 0.22).forward({}, {}, [x, y], True)[0],
        x + 0.22 * y, rtol=1e-6)
    np.testing.assert_array_equal(
        AddSeq("r", _pc(2), [t, t]).forward({}, {}, [x, y], True)[0], x + y)


# ---------------------------------------------------------------------------
# the whole model


def _kernel_model(b, s):
    """A model whose scans are the Pallas kernels' shapes (8 heads of 32,
    state 128, chunks of 128) and whose widths are whole lanes."""
    from flexflow_tpu.models.hybrid_ssm import HybridSSMConfig, HybridSSMLM

    cfg = _tiny_config(hidden_size=128, num_attention_heads=2,
                       num_key_value_heads=1, mamba_n_heads=8,
                       mamba_d_head=32, mamba_d_state=128,
                       mamba_chunk_size=128, max_position_embeddings=512,
                       vocab_size=256)
    return cfg, HybridSSMLM(HybridSSMConfig.from_config(
        cfg, batch_size=b, seq_length=s), MachineModel(jax.devices()[:1]))


@pytest.mark.parametrize("head", ["plain", "fused", "scan_kernels"])
def test_loss_and_every_operator_gradient_against_the_reference(
        head, tiny_model, pallas_kernels):
    """Seeded weights; ``fused``: a model of whole lanes with the kernel
    gate open, so that the tied head runs in the fused projection+CE
    kernel and the attention in the flash kernels, inside recomputed
    blocks; ``scan_kernels``: the same with scans of the shapes
    ``ff_ssd_fwd`` and ``ff_ssd_bwd`` take (the last chunk padded)."""
    from flexflow_tpu.models.hybrid_ssm import HybridSSMConfig, HybridSSMLM

    ff, cfg, b, s = tiny_model, _tiny_config(), 2, 20
    if head == "fused":
        cfg, b, s = _tiny_config(hidden_size=128, num_attention_heads=2,
                                 num_key_value_heads=1, mamba_n_heads=8,
                                 vocab_size=256), 4, 512
        ff = HybridSSMLM(HybridSSMConfig.from_config(
            cfg, batch_size=b, seq_length=s),
            MachineModel(jax.devices()[:1]))
    if head == "scan_kernels":
        (cfg, ff), b, s = _kernel_model(2, 200), 2, 200
    kernels = pallas_kernels if head != "plain" else contextlib.nullcontext
    if head == "fused":
        with kernels():
            assert ff._lm_head_fusion()
    form = "kernels.ssd.pallas.128x8x128"
    ran = _counted(form)
    plain = lm_check.check_against_reference(ff, cfg, _reference(), b, s,
                                             kernels)
    assert "lm_head" not in plain
    # two scans, each traced forward and in its recomputed block
    assert (_counted(form) > ran) == (head == "scan_kernels")


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_recomputed_step_equals_the_plain_step(path, tiny_model,
                                               pallas_kernels):
    """``kernels``: a model whose scans run ``ff_ssd_fwd`` and
    ``ff_ssd_bwd`` (and whose attention the flash kernels), so that a
    recomputed block runs the forward kernel once more and hands its
    backward the states it wrote."""
    model, s, vocab = tiny_model, 20, 96
    if path == "kernels":
        (_, model), s, vocab = _kernel_model(2, 256), 256, 256
    toks = jax.random.randint(jax.random.PRNGKey(7), (2, s), 0, vocab)
    params, state = model.init(3)
    before = jax.tree.map(np.asarray, params)
    blocks = model.recompute_blocks
    form = "kernels.ssd.pallas.128x8x128"
    ran = _counted(form)
    with pallas_kernels() if path == "kernels" else contextlib.nullcontext():
        out = model.make_train_step()(params, state, None, toks, toks)
        assert (_counted(form) > ran) == (path == "kernels")
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
    for a, b, p0 in zip(jax.tree.leaves(out[0]), jax.tree.leaves(plain[0]),
                        jax.tree.leaves(before)):
        np.testing.assert_allclose(a - p0, b - p0, rtol=1e-3, atol=1e-7)
    if path == "kernels":
        return
    # and on one fixed batch the loss falls
    step, (p, st), losses = model.make_train_step(), model.init(3), []
    for _ in range(8):
        p, st, _, loss = step(p, st, None, toks, toks)
        losses.append(float(loss))
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]


def test_operator_table_names_the_scan_and_holds_no_loop(tiny_model):
    """Every operator of the model is named in the compiled step, forward
    and backward, and the recurrence over chunks compiles to no ``while``
    whose body a trace would have to charge."""
    toks = jax.ShapeDtypeStruct((2, 20), jnp.int32)
    before = _counted("kernels.ssd.xla_chunked.12x4x16")
    table = tiny_model.operator_table(toks, toks)      # refuses nothing
    assert _counted("kernels.ssd.xla_chunked.12x4x16") >= before + 2
    assert _counted("ssm.chunk") == 12
    assert _counted("ssm.chunks_per_sequence") == 2
    seen = set(table.values())
    for name in ("blk0_ssm_in", "blk0_ssm_scan", "blk0_ssm_out",
                 "blk1_attn", "blk2_ssm_scan", "blk2_ffn"):
        assert (name, "forward") in seen and (name, "backward") in seen
    text = tiny_model.compile_train_step(toks, toks).as_text()
    assert " while(" not in text


def test_parameters_are_the_count_the_issue_reckons():
    """951 991 232 parameters at published widths, from the operators'
    own ``param_bytes`` (no array is made)."""
    from flexflow_tpu.models.hybrid_ssm import HybridSSMConfig, HybridSSMLM

    with open(CONFIG) as f:
        config = json.load(f)
    ff = HybridSSMLM(HybridSSMConfig.from_config(
        config, batch_size=2, seq_length=8192),
        MachineModel(jax.devices()[:1]))
    by_op = {op.name: op.param_bytes() // 4 for op in ff.layers}
    assert by_op["blk0_ssm_in"] == 17_432_576 + 21_760 + 64
    assert by_op["blk0_ssm_scan"] == 128
    assert by_op["blk0_ssm_out"] == 4096 + 8_388_608
    assert by_op["blk0_ffn"] == 50_331_648
    assert by_op["blk5_attn"] == 10_485_760
    assert by_op["embed"] == 205_520_896 and by_op["lm_head"] == 0
    mamba = sum(v for k, v in by_op.items() if k.startswith("blk0_"))
    attention = sum(v for k, v in by_op.items() if k.startswith("blk5_"))
    assert (mamba, attention) == (76_182_976, 60_821_504)
    assert sum(by_op.values()) == 951_991_232
    scan = ff.layers[[op.name for op in ff.layers].index("blk0_ssm_scan")]
    assert scan.flops_per_sample() == 8192 * 4_259_840


def test_apps_lm_trains_the_model_from_its_configuration_file():
    from flexflow_tpu.apps import lm

    lines = []
    out = lm.main(["--model-config", CONFIG, "--preset", "rehearsal",
                   "-b", "2", "-s", "32", "-i", "12", "--seed", "5"],
                  log=lines.append)
    assert any("2 mamba, 1 attention" in l for l in lines[:2])
    losses = out["loss"]
    # fresh random tokens every step: the loss stays at ln(vocabulary)
    assert len(losses) == 12 and np.all(np.isfinite(losses))
    assert np.all(np.abs(np.asarray(losses) - np.log(96)) < 0.1)
    with pytest.raises(SystemExit, match="positions"):
        lm.main(["--model-config", CONFIG, "--preset", "rehearsal",
                 "-s", "64", "-i", "1"], log=lines.append)
