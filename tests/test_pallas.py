"""Pallas flash-attention kernel: numeric parity with the XLA
streaming-softmax reference path (interpret mode on the CPU test mesh —
the identical kernel code compiles via Mosaic on TPU)."""

import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.parallel.ring_attention import blockwise_attention


def _rand(rng, *shape):
    return jnp.asarray(rng.randn(*shape).astype("float32"))


@contextlib.contextmanager
def flash_env(value="1"):
    """Set FLEXFLOW_TPU_FLASH for the block, restoring any pre-existing
    value afterwards (a bare pop would clobber a user-set value for the
    rest of the session)."""
    prev = os.environ.get("FLEXFLOW_TPU_FLASH")
    os.environ["FLEXFLOW_TPU_FLASH"] = value
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("FLEXFLOW_TPU_FLASH", None)
        else:
            os.environ["FLEXFLOW_TPU_FLASH"] = prev


def _dense_attention(q, k, v, causal):
    """(out, lse) of plain softmax attention in float32, (B, H, S, d)."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest"
                   ) / np.sqrt(q.shape[-1])
    if causal:
        qi = jnp.arange(q.shape[2])[:, None]
        s = jnp.where(qi >= jnp.arange(k.shape[2])[None, :], s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse[..., None]), v,
                     precision="highest")
    return out, lse


# id: (b, h, sq, sk, d), dtype, causal, (block_q, block_k), form, variant.
# One case for each way the kernels' variants split on the shapes
# (_layout, _Tiles, fused or split backward), at interpret-mode sizes.
_FLASH_CASES = {
    "hd64_bf16_causal_cell_layout":
        ((2, 4, 64, 64, 64), "bfloat16", True, (32, 32), "packed",
         "pack2.fused"),
    "hd64_f32_causal":
        ((2, 2, 64, 64, 64), "float32", True, (16, 16), "bhsd",
         "pack2.fused"),
    "hd128_one_head_a_block":
        ((1, 2, 40, 40, 128), "float32", True, (16, 16), "bhsd",
         "pack1.fused"),
    "hd32_four_heads_a_block":
        ((1, 4, 48, 48, 32), "float32", True, (16, 16), "packed",
         "pack4.fused"),
    "hd80_padded_to_128":
        ((1, 2, 40, 40, 80), "float32", True, (16, 16), "bhsd",
         "pad128.fused"),
    "odd_head_count_padded":
        ((1, 3, 24, 24, 64), "float32", False, (16, 16), "packed",
         "pad128.fused"),
    "hd8_noncausal":
        ((2, 3, 16, 16, 8), "float32", False, (None, None), "bhsd",
         "pad128.fused"),
    "hd16_default_blocks":
        ((1, 2, 40, 40, 16), "float32", True, (None, None), "bhsd",
         "pad128.fused"),
    "length_not_a_block_multiple":
        ((1, 2, 20, 20, 64), "float32", True, (16, 16), "bhsd",
         "pack2.fused"),
    "padded_keys_noncausal":
        ((1, 2, 24, 20, 64), "float32", False, (16, 16), "bhsd",
         "pack2.fused"),
    "padded_keys_below_the_diagonal":
        ((1, 2, 48, 24, 64), "float32", True, (16, 16), "bhsd",
         "pack2.fused"),
    "sq_shorter_than_sk":
        ((1, 2, 16, 48, 64), "float32", True, (16, 16), "bhsd",
         "pack2.fused"),
    "sq_longer_than_sk":
        ((1, 2, 48, 16, 64), "float32", True, (16, 16), "bhsd",
         "pack2.fused"),
    "rectangular_blocks":
        ((1, 2, 64, 64, 64), "float32", True, (32, 16), "bhsd",
         "pack2.fused"),
    "four_by_four_pieces_padded_length":
        ((1, 2, 72, 72, 64), "float32", True, (32, 32), "bhsd",
         "pack2.fused"),
    "wide_key_blocks":
        ((1, 2, 64, 64, 64), "float32", True, (16, 32), "bhsd",
         "pack2.fused"),
    "split_backward":
        ((1, 2, 48, 48, 64), "float32", True, (16, 16), "bhsd",
         "pack2.split"),
    "split_backward_padded_bf16":
        ((1, 2, 40, 24, 80), "bfloat16", False, (16, 16), "packed",
         "pad128.split"),
    "partial_with_lse_cotangent":
        ((1, 2, 32, 48, 64), "float32", False, (16, 16), "partial",
         "pack2.fused"),
    "partial_causal_bf16":
        ((1, 2, 32, 32, 64), "bfloat16", True, (16, 16), "partial",
         "pack2.fused"),
}


@pytest.fixture
def small_flash(monkeypatch):
    """The flash module with its diagonal pieces cut to test sizes (16 x 16
    blocks: one forward piece, 2 x 2 backward pieces; 32 x 32: 2 x 2 and
    4 x 4), so interpret mode walks what the chip walks at 1024."""
    import importlib

    fa = importlib.import_module("flexflow_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_FWD_PIECE", 16)
    monkeypatch.setattr(fa, "_BWD_PIECE", 8)
    fa._make_flash.cache_clear()
    yield fa
    fa._make_flash.cache_clear()


@pytest.mark.parametrize("case", sorted(_FLASH_CASES))
def test_flash_parity(case, small_flash, monkeypatch):
    """Forward and all three gradients against plain attention, the
    result's type, and the variant the shapes selected."""
    from flexflow_tpu import obs

    fa = small_flash
    (b, h, sq, sk, d), dtype, causal, (bq, bk), form, variant = \
        _FLASH_CASES[case]
    if variant.endswith("split"):
        monkeypatch.setattr(fa, "_FUSED_DQ_BYTES", 0)
    rng = np.random.RandomState(2)
    q = _rand(rng, b, h, sq, d).astype(dtype)
    k, v = (_rand(rng, b, h, sk, d).astype(dtype) for _ in range(2))
    w, u = _rand(rng, b, h, sq, d), _rand(rng, b, h, sq)

    def pack(x):
        return x.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[2], -1)

    def flash(q, k, v):
        kw = dict(causal=causal, block_q=bq, block_k=bk)
        if form == "partial":
            return fa.flash_attention_partial(q, k, v, **kw)
        if form == "packed":
            out = fa.flash_attention_packed(pack(q), pack(k), pack(v), h,
                                            **kw)
            return out.reshape(b, sq, h, d).transpose(0, 2, 1, 3), None
        return fa.flash_attention(q, k, v, **kw), None

    def loss(attn):
        def f(q, k, v):
            out, lse = attn(q, k, v)
            total = (out.astype(jnp.float32) * w).sum()
            if form == "partial":  # a non-zero lse cotangent
                total = total + (lse * u).sum()
            return total, (out, lse)
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    name = f"kernels.flash.{variant}"
    before = obs.snapshot()["counters"].get(name, 0)
    (_, (out, lse)), grads = loss(flash)(q, k, v)
    assert obs.snapshot()["counters"].get(name, 0) == before + 1

    (_, (ref_out, ref_lse)), ref_grads = loss(
        lambda q, k, v: _dense_attention(q, k, v, causal))(q, k, v)
    assert out.dtype == (jnp.float32 if form == "partial" else q.dtype)
    tol = 3e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out), rtol=tol, atol=tol)
    if form == "partial":
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                                   rtol=tol, atol=tol)
    gtol = 6e-2 if dtype == "bfloat16" else 1e-4
    for got, want, x in zip(grads, ref_grads, (q, k, v)):
        assert got.dtype == x.dtype  # cotangents in the primal's type
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=gtol, atol=gtol)


def _top_level_eqns(jaxpr):
    """Equations XLA sees around the kernels: everything but the bodies
    of the pallas_calls."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _top_level_eqns(sub)


def test_flash_cell_layout_has_no_pad_and_no_float32_copy():
    """At the GPT-2 cell's layout (bf16, hd 64, S 1024, causal, the
    projections' (B, S, H*hd)) nothing is padded or widened in HBM: no
    pad, no transpose, no float32 array as large as a padded
    (B*H, S, 128); one forward and one backward kernel; the result and
    the gradients in the operands' type; one count a traced call."""
    from flexflow_tpu import obs
    from flexflow_tpu.ops.pallas.flash_attention import \
        flash_attention_packed

    b, s, h, hd = 2, 1024, 12, 64
    x = jax.ShapeDtypeStruct((b, s, h * hd), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention_packed(q, k, v, h, causal=True)
        assert out.dtype == jnp.bfloat16 and out.shape == (b, s, h * hd)
        return out.astype(jnp.float32).sum()

    name = "kernels.flash.pack2.fused"
    before = obs.snapshot()["counters"].get(name, 0)
    closed = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x)
    assert obs.snapshot()["counters"][name] == before + 1
    assert all(v.aval.dtype == jnp.bfloat16 for v in closed.jaxpr.outvars)
    eqns = list(_top_level_eqns(closed.jaxpr))
    names = [e.primitive.name for e in eqns]
    assert "pad" not in names and "transpose" not in names, names
    kernels = [e.params["name"] for e in eqns
               if e.primitive.name == "pallas_call"]
    assert kernels == ["ff_flash_fwd", "ff_flash_bwd"], kernels
    for e in eqns:
        for var in e.outvars:
            aval = var.aval
            assert not (aval.dtype == jnp.float32
                        and aval.size >= b * h * s * 128), (e, aval)


@pytest.mark.parametrize("backward", ["fused", "split"])
@pytest.mark.parametrize("form", ["packed", "partial"])
def test_a_checkpoint_that_keeps_the_named_results_runs_the_forward_once(
        form, backward, small_flash, monkeypatch):
    """The forward kernel names its two results (KEPT_RESULTS).  Under a
    bare jax.checkpoint the backward runs the kernel once more to have
    them again; under the policy a recomputed block uses
    (FFModel._run_recomputed) they are kept, the kernel runs once, and
    the backward kernels read the very arrays they read without a
    checkpoint: all three gradients are equal bit for bit."""
    from flexflow_tpu.ops.pallas import KEPT_RESULTS

    fa = small_flash
    assert KEPT_RESULTS == ("ff_flash_out", "ff_flash_lse")
    if backward == "split":
        monkeypatch.setattr(fa, "_FUSED_DQ_BYTES", 0)
    b, h, s, d = 1, 2, 32, 64
    rng = np.random.RandomState(11)
    kw = dict(causal=True, block_q=16, block_k=16)
    if form == "packed":
        q, k, v = (_rand(rng, b, s, h * d) for _ in range(3))
        w = _rand(rng, b, s, h * d)

        def f(q, k, v):
            out = fa.flash_attention_packed(q * 2.0, k, v, h, **kw)
            return (out * w).sum()
    else:
        q, k, v = (_rand(rng, b, h, s, d) for _ in range(3))
        w, u = _rand(rng, b, h, s, d), _rand(rng, b, h, s)

        def f(q, k, v):
            out, lse = fa.flash_attention_partial(q * 2.0, k, v, **kw)
            return (out * w).sum() + (lse * u).sum()

    def kernels(g):
        closed = jax.make_jaxpr(jax.grad(g, argnums=(0, 1, 2)))(q, k, v)
        return sorted(e.params["name"] for e in _top_level_eqns(closed.jaxpr)
                      if e.primitive.name == "pallas_call")

    bwd = ["ff_flash_bwd"] if backward == "fused" \
        else ["ff_flash_bwd_dkv", "ff_flash_bwd_dq"]
    keeping = jax.checkpoint(
        f, policy=jax.checkpoint_policies.save_only_these_names(
            *KEPT_RESULTS))
    assert kernels(f) == bwd + ["ff_flash_fwd"]
    assert kernels(jax.checkpoint(f)) == bwd + ["ff_flash_fwd"] * 2
    assert kernels(keeping) == bwd + ["ff_flash_fwd"]
    want = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(keeping, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_partial_combine_matches_full():
    """Two K/V chunks merged by combine_partials == one full attention."""
    from flexflow_tpu.ops.pallas.flash_attention import (
        combine_partials, flash_attention_partial)

    rng = np.random.RandomState(5)
    q, k, v = (_rand(rng, 2, 2, 32, 8) for _ in range(3))
    o1, l1 = flash_attention_partial(q, k[:, :, :16], v[:, :, :16])
    o2, l2 = flash_attention_partial(q, k[:, :, 16:], v[:, :, 16:])
    o, _ = combine_partials(o1, l1, o2, l2)
    ref = blockwise_attention(q, k, v, False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    def loss_pc(q, k, v):
        o1, l1 = flash_attention_partial(q, k[:, :, :16], v[:, :, :16])
        o2, l2 = flash_attention_partial(q, k[:, :, 16:], v[:, :, 16:])
        return (combine_partials(o1, l1, o2, l2)[0] ** 2).sum()

    g1 = jax.grad(loss_pc, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: (blockwise_attention(q, k, v, False) ** 2)
                  .sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_flash_path(machine8, causal):
    """Ring attention on the Pallas partial kernel == global reference,
    values and gradients, on a 4-way sequence mesh."""
    from jax.sharding import Mesh

    from flexflow_tpu.parallel.ring_attention import ring_attention

    rng = np.random.RandomState(6)
    q, k, v = (_rand(rng, 2, 2, 32, 8) for _ in range(3))
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4,), ("s",))
    ref = blockwise_attention(q, k, v, causal)
    gref = jax.grad(lambda q, k, v: (blockwise_attention(q, k, v, causal)
                                     ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    with flash_env():
        got = ring_attention(q, k, v, mesh, "s", causal)
        gfl = jax.grad(lambda q, k, v: (ring_attention(q, k, v, mesh, "s",
                                                       causal) ** 2).sum(),
                       argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(gfl, gref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_transformer_forward_matches_with_flash_forced(machine8):
    """End-to-end: forcing the flash path (shard-mapped over the canonical
    DP grid) must reproduce the default XLA attention loss."""
    from flexflow_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)

    tcfg = TransformerConfig(batch_size=8, seq_length=16, num_layers=1,
                             d_model=16, num_heads=4, d_ff=32, vocab_size=32,
                             causal=True)
    toks = jnp.asarray(np.random.RandomState(4).randint(0, 32, (8, 16)),
                       "int32")

    def run():
        tlm = TransformerLM(tcfg, machine8)
        params, state = tlm.init(seed=0)
        loss, _ = tlm.loss_fn(params, state, toks, toks, train=True)
        return float(loss)

    base = run()
    with flash_env():
        flashed = run()
    assert abs(base - flashed) < 1e-4, (base, flashed)


def test_fused_linear_ce_parity():
    from flexflow_tpu.ops.pallas.fused_ce import fused_linear_ce

    rng = np.random.RandomState(7)
    n, d, v = 40, 24, 100
    x = jnp.asarray(rng.randn(n, d), "float32")
    w = jnp.asarray(rng.randn(d, v) * 0.1, "float32")
    b = jnp.asarray(rng.randn(v) * 0.1, "float32")
    lab = jnp.asarray(rng.randint(0, v, (n,)), "int32")

    def ref(x, w, b):
        lp = jax.nn.log_softmax(x @ w + b, axis=-1)
        return -jnp.take_along_axis(lp, lab[:, None], axis=1)[:, 0]

    got = fused_linear_ce(x, w, b, lab, block_n=16, block_v=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref(x, w, b)),
                               rtol=1e-5, atol=1e-5)
    wgt = jnp.arange(1.0, n + 1)  # weighted cotangent exercises g scaling
    g1 = jax.grad(lambda x, w, b: (fused_linear_ce(
        x, w, b, lab, block_n=16, block_v=16) * wgt).sum(),
        argnums=(0, 1, 2))(x, w, b)
    g2 = jax.grad(lambda x, w, b: (ref(x, w, b) * wgt).sum(),
                  argnums=(0, 1, 2))(x, w, b)
    for a, c in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-4, atol=1e-4)


def test_lm_head_fusion_matches_unfused(machine8):
    """The apply-time RnnLinear->SoftmaxDP fusion must reproduce the
    unfused training loss (here under the shard-mapped DP path)."""
    from flexflow_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)

    tcfg = TransformerConfig(batch_size=8, seq_length=256, num_layers=1,
                             d_model=16, num_heads=4, d_ff=32,
                             vocab_size=64, causal=True)
    toks = jnp.asarray(np.random.RandomState(8).randint(0, 64, (8, 256)),
                       "int32")

    def run():
        tlm = TransformerLM(tcfg, machine8)
        params, state = tlm.init(seed=0)
        loss, _ = tlm.loss_fn(params, state, toks, toks, train=True)
        return float(loss)

    base = run()
    with flash_env():
        fused = run()
    assert abs(base - fused) < 1e-3, (base, fused)


def test_lm_head_fusion_grads_match(machine8):
    """Gradients through the fused head equal the unfused path."""
    from flexflow_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)

    tcfg = TransformerConfig(batch_size=8, seq_length=256, num_layers=1,
                             d_model=16, num_heads=4, d_ff=32,
                             vocab_size=64, causal=True)
    toks = jnp.asarray(np.random.RandomState(9).randint(0, 64, (8, 256)),
                       "int32")

    def grads():
        tlm = TransformerLM(tcfg, machine8)
        params, state = tlm.init(seed=0)
        g = jax.grad(lambda p: tlm.loss_fn(p, state, toks, toks,
                                           train=True)[0])(params)
        return jax.tree.leaves(g)

    base = grads()
    with flash_env():
        fused = grads()
    for a, c in zip(base, fused):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-3, atol=2e-3)


def test_lm_head_fusion_vocab_tp(machine8):
    """Vocab-TP fused head (c=4 x n=2 grid, per-shard kernels + lse/corr
    combine) == unfused GSPMD loss and grads."""
    from flexflow_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from flexflow_tpu.strategy import ParallelConfig, Strategy

    s = Strategy()
    s["lm_head"] = ParallelConfig((4, 2), tuple(range(8)))
    tcfg = TransformerConfig(batch_size=8, seq_length=256, num_layers=1,
                             d_model=16, num_heads=4, d_ff=32,
                             vocab_size=64, causal=True)
    toks = jnp.asarray(np.random.RandomState(10).randint(0, 64, (8, 256)),
                       "int32")

    def run(fused):
        ctx = flash_env() if fused else flash_env("0")
        with ctx:
            tlm = TransformerLM(tcfg, machine8, s)
            params, state = tlm.init(seed=0)
            loss, _ = tlm.loss_fn(params, state, toks, toks, train=True)
            g = jax.grad(lambda p: tlm.loss_fn(p, state, toks, toks,
                                               train=True)[0])(params)
            return float(loss), jax.tree.leaves(g)

    base_loss, base_g = run(False)
    fused_loss, fused_g = run(True)
    assert abs(base_loss - fused_loss) < 1e-3, (base_loss, fused_loss)
    for a, c in zip(base_g, fused_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# Pallas max-pool backward (ops/pallas/maxpool.py): parity with XLA
# reduce_window autodiff — including first-max tie-breaking (integer-valued
# inputs make ties certain) and the fused-ReLU sentinel path.


def _ref_maxpool(x, kh, kw, ph, pw, relu):
    from jax import lax

    y = lax.reduce_window(x, -jnp.inf, lax.max, (1, kh, kw, 1),
                          (1, 2, 2, 1), ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    return jax.nn.relu(y) if relu else y


@pytest.mark.parametrize("n,h,w,c,k,p,relu", [
    (2, 9, 9, 3, 3, 0, False),    # odd extents, VALID (Inception pools)
    (2, 16, 16, 5, 3, 0, True),   # even extents + fused relu
    (3, 15, 17, 4, 3, 1, True),   # pad 1 (ResNet/DenseNet pool1), h != w
    (2, 12, 12, 3, 2, 0, False),  # 2x2 (VGG pools)
    (1, 8, 8, 2, 3, 1, False),    # tiny single-sample
    (2, 23, 19, 6, 3, 0, True),   # ragged H/W blocks
])
def test_maxpool_parity(n, h, w, c, k, p, relu):
    from flexflow_tpu.ops.pallas.maxpool import maxpool2d

    rng = np.random.RandomState(0)
    # small-integer inputs: every window has ties, negatives exercise the
    # relu-clamped sentinel
    x = jnp.asarray(rng.randint(-3, 4, size=(n, h, w, c)), jnp.float32)
    g = jnp.asarray(rng.randn(n, *_ref_maxpool(x, k, k, p, p, relu).shape[1:3],
                              c), jnp.float32)

    def f_pallas(x):
        return maxpool2d(x, k, k, p, p, relu, interpret=True)

    def f_ref(x):
        return _ref_maxpool(x, k, k, p, p, relu)

    np.testing.assert_array_equal(np.asarray(f_pallas(x)),
                                  np.asarray(f_ref(x)))
    gp = jax.grad(lambda x: jnp.vdot(f_pallas(x), g))(x)
    gr = jax.grad(lambda x: jnp.vdot(f_ref(x), g))(x)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               rtol=1e-6, atol=1e-6)


def test_maxpool_supported_gate():
    from flexflow_tpu.ops.pallas.maxpool import supported

    assert supported(3, 3, 2, 2, 0, 0)
    assert supported(3, 3, 2, 2, 1, 1)
    assert supported(2, 2, 2, 2, 0, 0)
    assert not supported(3, 3, 1, 1, 1, 1)        # stride-1 pools stay XLA
    assert not supported(5, 5, 2, 2, 0, 0)        # unsupported kernel size
    assert not supported(3, 3, 2, 2, 0, 0, "avg")  # avg pools stay XLA


# ---------------------------------------------------------------------------
# Pallas avg-pool backward (ops/pallas/avgpool.py): the non-overlapping /
# global geometries where dx is a pure block upsample of dy — parity with
# the canonical sum/count reduce_window pair under autodiff, including the
# fused-ReLU mask from the pooled-output residual.


def _ref_avgpool(x, kh, kw, sh, sw, relu):
    from jax import lax

    ones = jnp.ones_like(x)
    s = lax.reduce_window(x, 0.0, lax.add, (1, kh, kw, 1), (1, sh, sw, 1),
                          ((0, 0),) * 4)
    cnt = lax.reduce_window(ones, 0.0, lax.add, (1, kh, kw, 1),
                            (1, sh, sw, 1), ((0, 0),) * 4)
    y = s / cnt
    return jax.nn.relu(y) if relu else y


@pytest.mark.parametrize("relu", [False, True])
@pytest.mark.parametrize("n,h,w,c,kh,kw,sh,sw", [
    (2, 8, 8, 16, 8, 8, 1, 1),    # global pool, stride 1 (Inception tail)
    (4, 8, 8, 3, 2, 2, 2, 2),     # 2x2 exact tiling, ragged C block
    (2, 12, 9, 24, 3, 3, 3, 3),   # 3x3 tiling, h != w
])
def test_avgpool_parity(n, h, w, c, kh, kw, sh, sw, relu):
    from flexflow_tpu.ops.pallas.avgpool import avgpool2d, supported

    assert supported(kh, kw, sh, sw, 0, 0, h, w)
    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.randn(n, h, w, c), jnp.float32)

    def f_pallas(x):
        return avgpool2d(x, kh, kw, sh, sw, 0, 0, relu, interpret=True)

    def f_ref(x):
        return _ref_avgpool(x, kh, kw, sh, sw, relu)

    y = f_pallas(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(f_ref(x)),
                               rtol=1e-6, atol=1e-6)
    g = jnp.asarray(rng.randn(*y.shape), jnp.float32)
    gp = jax.grad(lambda x: jnp.vdot(f_pallas(x), g))(x)
    gr = jax.grad(lambda x: jnp.vdot(f_ref(x), g))(x)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               rtol=1e-6, atol=1e-6)


def test_avgpool_supported_gate():
    from flexflow_tpu.ops.pallas.avgpool import supported

    assert supported(8, 8, 1, 1, 0, 0, 8, 8)       # global, any stride
    assert supported(2, 2, 2, 2, 0, 0, 12, 12)     # exact tiling
    assert not supported(3, 3, 1, 1, 1, 1, 35, 35)  # overlap/pad stay XLA
    assert not supported(3, 3, 3, 3, 0, 0, 10, 10)  # remainder rows
    assert not supported(2, 2, 2, 2, 0, 0, 12, 12, "max")  # max stays XLA


def test_pool2d_avg_routes_through_pallas_when_enabled(monkeypatch):
    from flexflow_tpu.ops.base import Tensor
    from flexflow_tpu.ops.pool import POOL_AVG, Pool2D
    from flexflow_tpu.strategy import ParallelConfig

    monkeypatch.setenv("FLEXFLOW_TPU_AVGPOOL", "1")
    t = Tensor((2, 8, 8, 16))
    op = Pool2D("p", ParallelConfig((1, 1, 1, 1), (0,)), t, 8, 8, 1, 1,
                0, 0, POOL_AVG, relu=True)
    assert op._use_pallas(None)
    rng = np.random.RandomState(12)
    x = jnp.asarray(rng.randn(2, 8, 8, 16), jnp.float32)
    y_pal, _ = op.forward({}, {}, [x], train=True)
    monkeypatch.setenv("FLEXFLOW_TPU_AVGPOOL", "0")
    assert not op._use_pallas(None)
    y_xla, _ = op.forward({}, {}, [x], train=True)
    # 1/64 is a power of two: the kernel's constant-scale forward is
    # bit-equal to the XLA path's sum/count divide here
    np.testing.assert_array_equal(np.asarray(y_pal), np.asarray(y_xla))


# ---------------------------------------------------------------------------
# Fused batchnorm normalize+ReLU (ops/pallas/bn_act.py): one-pass backward
# emitting dx plus both per-channel sums — parity with the unfused XLA
# chain under autodiff for values and all three gradients.


def _ref_bn_act(x, inv, shift, relu):
    y = x * inv.astype(x.dtype) + shift.astype(x.dtype)
    return jax.nn.relu(y) if relu else y


@pytest.mark.parametrize("relu", [False, True])
@pytest.mark.parametrize("n,h,w,c", [
    (4, 4, 4, 16),    # single channel block
    (4, 4, 4, 130),   # ragged C block (gc = 2, 2-lane tail)
    (8, 1, 1, 7),     # post-flatten-like tiny channels
])
def test_bn_act_parity(n, h, w, c, relu):
    from flexflow_tpu.ops.pallas.bn_act import bn_act, supported

    assert supported(n, h, w, c)
    rng = np.random.RandomState(13)
    x = jnp.asarray(rng.randn(n, h, w, c), jnp.float32)
    inv = jnp.asarray(rng.randn(c), jnp.float32)
    shift = jnp.asarray(rng.randn(c), jnp.float32)
    g = jnp.asarray(rng.randn(n, h, w, c), jnp.float32)

    def f_pallas(x, inv, shift):
        return bn_act(x, inv, shift, relu=relu, interpret=True)

    np.testing.assert_allclose(
        np.asarray(f_pallas(x, inv, shift)),
        np.asarray(_ref_bn_act(x, inv, shift, relu)), rtol=1e-6, atol=1e-6)
    gp = jax.grad(lambda *a: jnp.vdot(f_pallas(*a), g),
                  argnums=(0, 1, 2))(x, inv, shift)
    gr = jax.grad(lambda *a: jnp.vdot(_ref_bn_act(*a, relu), g),
                  argnums=(0, 1, 2))(x, inv, shift)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_bn_act_supported_gate():
    from flexflow_tpu.ops.pallas.bn_act import supported

    assert supported(8, 4, 4, 64)
    # M = 50 has no power-of-two row-block divisor: ragged rows would
    # pollute the channel-sum accumulators, so the gate refuses
    assert not supported(2, 5, 5, 64)


def test_bn_act_bf16_inputs():
    from flexflow_tpu.ops.pallas.bn_act import bn_act

    rng = np.random.RandomState(14)
    x = jnp.asarray(rng.randn(4, 4, 4, 16), jnp.bfloat16)
    inv = jnp.asarray(rng.randn(16), jnp.float32)
    shift = jnp.asarray(rng.randn(16), jnp.float32)
    y = bn_act(x, inv, shift, relu=True, interpret=True)
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(y, np.float32),
        np.asarray(_ref_bn_act(x, inv, shift, True), np.float32),
        rtol=2e-2, atol=2e-2)
    gx = jax.grad(lambda x: bn_act(x, inv, shift, relu=True,
                                   interpret=True).astype(jnp.float32)
                  .sum())(x)
    assert gx.dtype == jnp.bfloat16  # cotangents in the primal dtype


def test_batchnorm_routes_through_pallas_when_enabled(monkeypatch):
    """BatchNorm.forward takes the fused kernel under the env gate; loss
    values, running stats, and the FULL gradient chain (through the
    folded statistics, not just the elementwise tail) match the XLA
    path."""
    from flexflow_tpu.ops.base import Tensor
    from flexflow_tpu.ops.norm import BatchNorm
    from flexflow_tpu.strategy import ParallelConfig

    t = Tensor((4, 8, 8, 16))
    bn = BatchNorm("b", ParallelConfig((1, 1, 1, 1), (0,)), t, relu=True)
    rng = np.random.RandomState(15)
    x = jnp.asarray(rng.randn(4, 8, 8, 16), jnp.float32)
    params = bn.init_params(jax.random.PRNGKey(0))
    params = {"scale": params["scale"] + 0.3, "bias": params["bias"] - 0.1}
    state = bn.init_state()

    def run(p):
        y, st = bn.forward(p, state, [x], train=True)
        return jnp.sum(y * y), (y, st)

    monkeypatch.setenv("FLEXFLOW_TPU_BNRELU", "1")
    assert bn._use_pallas(x)
    (l1, (y1, st1)), g1 = jax.value_and_grad(run, has_aux=True)(params)
    monkeypatch.setenv("FLEXFLOW_TPU_BNRELU", "0")
    assert not bn._use_pallas(x)
    (l2, (y2, st2)), g2 = jax.value_and_grad(run, has_aux=True)(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-5)
    for k in st1:
        np.testing.assert_array_equal(np.asarray(st1[k]), np.asarray(st2[k]))
    for k in g1:
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g2[k]),
                                   rtol=1e-4, atol=1e-4)


def test_pool2d_routes_through_pallas_when_enabled(monkeypatch):
    """Pool2D.forward takes the kernel path under the env gate and the
    result matches the XLA path bit-for-bit (interpret mode)."""
    from flexflow_tpu.ops.base import Tensor
    from flexflow_tpu.ops.pool import Pool2D
    from flexflow_tpu.strategy import ParallelConfig

    monkeypatch.setenv("FLEXFLOW_TPU_MAXPOOL", "1")
    t = Tensor((2, 64, 64, 3))
    op = Pool2D("p", ParallelConfig((1, 1, 1, 1), (0,)), t, 3, 3, 2, 2,
                0, 0, relu=True)
    assert op._use_pallas(None)
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randint(-2, 3, size=(2, 64, 64, 3)), jnp.float32)
    y_pal, _ = op.forward({}, {}, [x], train=True)
    monkeypatch.setenv("FLEXFLOW_TPU_MAXPOOL", "0")
    assert not op._use_pallas(None)
    y_xla, _ = op.forward({}, {}, [x], train=True)
    np.testing.assert_array_equal(np.asarray(y_pal), np.asarray(y_xla))


# ---------------------------------------------------------------------------
# Round-13 routing policy: one --pallas auto|on|off switch (installed by
# FFModel from FFConfig.pallas) + the per-geometry maxpool cost model
# that replaces the old min(h, w) >= 48 size guess under auto.


def test_set_policy_validates_eagerly():
    from flexflow_tpu.ops import pallas

    before = pallas.get_policy()
    with pytest.raises(ValueError):
        pallas.set_policy("sometimes")
    assert pallas.get_policy() == before


def test_policy_forced_modes(monkeypatch):
    from flexflow_tpu.ops import pallas

    for var in ("FLEXFLOW_TPU_FLASH", "FLEXFLOW_TPU_MAXPOOL",
                "FLEXFLOW_TPU_AVGPOOL", "FLEXFLOW_TPU_BNRELU"):
        monkeypatch.delenv(var, raising=False)
    try:
        pallas.set_policy("on")
        assert pallas.flash_enabled() and pallas.maxpool_enabled()
        assert pallas.avgpool_enabled() and pallas.bnrelu_enabled()
        assert not pallas.maxpool_cost_gated()  # forced: no cost model
        pallas.set_policy("off")
        assert not (pallas.flash_enabled() or pallas.maxpool_enabled()
                    or pallas.avgpool_enabled() or pallas.bnrelu_enabled())
        pallas.set_policy("auto")
        # CPU backend: TPU-candidate kernels off, pending-measurement
        # kernels (avgpool/bnrelu) off by design until a TPU run says so
        assert not pallas.maxpool_enabled()
        assert not pallas.avgpool_enabled()
        assert pallas.maxpool_cost_gated()
    finally:
        pallas.set_policy("auto")


def test_env_vars_override_policy_per_kernel(monkeypatch):
    from flexflow_tpu.ops import pallas

    try:
        pallas.set_policy("off")
        monkeypatch.setenv("FLEXFLOW_TPU_MAXPOOL", "1")
        assert pallas.maxpool_enabled()          # env beats policy off
        assert not pallas.maxpool_cost_gated()   # explicit = no gate
        assert not pallas.avgpool_enabled()      # other kernels stay off
        pallas.set_policy("on")
        monkeypatch.setenv("FLEXFLOW_TPU_MAXPOOL", "0")
        assert not pallas.maxpool_enabled()      # env beats policy on
        assert pallas.bnrelu_enabled()
    finally:
        pallas.set_policy("auto")


def test_ffmodel_installs_the_policy(machine1):
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.model import FFModel
    from flexflow_tpu.ops import pallas

    try:
        FFModel(FFConfig(batch_size=8, input_height=16, input_width=16,
                         num_classes=8, pallas="off"), machine1)
        assert pallas.get_policy() == "off"
    finally:
        pallas.set_policy("auto")


def test_maxpool_cost_model_prices_both_sides():
    from flexflow_tpu.ops.pallas.maxpool import roofline_predicted_win_ms

    # Inception's first big pool (2, 147, 147, 64), 3x3/2 pad 0: in f32
    # the backward byte saving beats the extra forward sel-plane pass...
    assert roofline_predicted_win_ms(2, 147, 147, 64, 3, 0, 4) > 0
    # ...in bf16 it does not (x halves, the bf16 sel plane does not) —
    # reproducing the measured end-to-end neutrality of the naive swap
    assert roofline_predicted_win_ms(2, 147, 147, 64, 3, 0, 2) < 0
    # deeper window, same trend but monotone in the input byte volume
    assert roofline_predicted_win_ms(2, 147, 147, 64, 3, 0, 4) > \
        roofline_predicted_win_ms(2, 71, 71, 64, 3, 0, 4)


def test_pool2d_auto_routes_by_predicted_win(monkeypatch):
    from flexflow_tpu.ops import pallas
    from flexflow_tpu.ops.base import Tensor
    from flexflow_tpu.ops.pool import Pool2D
    from flexflow_tpu.strategy import ParallelConfig

    monkeypatch.delenv("FLEXFLOW_TPU_MAXPOOL", raising=False)
    # stand in for the TPU-backend candidacy so auto reaches the model
    monkeypatch.setattr(pallas, "maxpool_enabled", lambda: True)
    try:
        pallas.set_policy("auto")
        pc = ParallelConfig((1, 1, 1, 1), (0,))
        op32 = Pool2D("p32", pc, Tensor((2, 147, 147, 64)), 3, 3, 2, 2,
                      0, 0, relu=False)
        assert op32._use_pallas(None)        # f32: predicted win
        op16 = Pool2D("p16", pc, Tensor((2, 147, 147, 64), "bfloat16"),
                      3, 3, 2, 2, 0, 0, relu=False)
        assert not op16._use_pallas(None)    # bf16: predicted loss
        pallas.set_policy("on")
        assert op16._use_pallas(None)        # forced mode skips the gate
    finally:
        pallas.set_policy("auto")
