"""Pallas flash-attention kernel: numeric parity with the XLA
streaming-softmax reference path (interpret mode on the CPU test mesh —
the identical kernel code compiles via Mosaic on TPU)."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.parallel.ring_attention import blockwise_attention


def _rand(rng, *shape):
    return jnp.asarray(rng.randn(*shape).astype("float32"))


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
def test_ring_attention_flash_path(machine8, causal, pallas_kernels):
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
    with pallas_kernels():
        got = ring_attention(q, k, v, mesh, "s", causal)
        gfl = jax.grad(lambda q, k, v: (ring_attention(q, k, v, mesh, "s",
                                                       causal) ** 2).sum(),
                       argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(gfl, gref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_transformer_forward_matches_with_flash_forced(machine8, pallas_kernels):
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
    with pallas_kernels():
        flashed = run()
    assert abs(base - flashed) < 1e-4, (base, flashed)


# (n, v, block_n, block_v): token blocks x vocabulary blocks of the grid
CE_TILES = [
    (40, 100, 16, 16),    # 3 x 7, neither size a multiple of its tile
    (40, 100, 40, 104),   # 1 x 1
    (40, 100, 40, 16),    # 1 x 7: dW and db are written once and never read
    (40, 100, 16, 104),   # 3 x 1: the sum stays in the output block
    (48, 96, 16, 32),     # 3 x 3, both sizes whole tiles
    (40, 100, 8, 56),     # 5 x 2
]


@pytest.mark.parametrize("interpreter", ["generic", "hbm"])
@pytest.mark.parametrize("form", ["plain", "partial"])
@pytest.mark.parametrize("n,v,block_n,block_v", CE_TILES)
def test_fused_linear_ce_parity(n, v, block_n, block_v, form, interpreter):
    """Forward and the one backward kernel against log_softmax, under a
    cotangent that differs row by row.  The backward sums dW and db over
    the token blocks through HBM (an input aliased to the output): the
    ``hbm`` interpreter models that buffer as the chip has it, the
    generic one (what every CPU run of a model takes) keeps the two
    apart and the kernel reads its earlier sum from the output block.
    ``partial``: the vocabulary-sharded form, labels outside the slice
    (below 0, in the padded tail, beyond it) and a cotangent on ``lse``."""
    from jax.experimental.pallas import tpu as pltpu

    from flexflow_tpu.ops.pallas.fused_ce import (fused_linear_ce,
                                                  fused_linear_ce_partial)

    rng = np.random.RandomState(7)
    d = 24
    x = jnp.asarray(rng.randn(n, d), "float32")
    w = jnp.asarray(rng.randn(d, v) * 0.1, "float32")
    b = jnp.asarray(rng.randn(v) * 0.1, "float32")
    partial = form == "partial"
    lab = jnp.asarray(rng.randint(-v if partial else 0,
                                  2 * v if partial else v, (n,)), "int32")
    lab = lab.at[0].set(v + 1) if partial else lab   # in the padded tail
    interpret = True if interpreter == "generic" else pltpu.InterpretParams()
    wgt = jnp.arange(1.0, n + 1)    # weighted cotangents exercise g scaling
    wgt_lse = jnp.cos(jnp.arange(n, dtype=jnp.float32))

    def ref(x, w, b):
        logits = x @ w + b
        lse = jax.nn.logsumexp(logits, axis=-1)
        inside = (lab >= 0) & (lab < v)
        corr = jnp.take_along_axis(
            logits, jnp.clip(lab, 0, v - 1)[:, None], axis=1)[:, 0]
        return lse - jnp.where(inside, corr, 0.0), lse

    def kernel(x, w, b):
        if partial:
            return fused_linear_ce_partial(x, w, b, lab, block_n=block_n,
                                           block_v=block_v,
                                           interpret=interpret)
        return fused_linear_ce(x, w, b, lab, block_n=block_n,
                               block_v=block_v, interpret=interpret), 0.0

    def weighted(f):
        def loss(x, w, b):
            nll, lse = f(x, w, b)
            return (nll * wgt).sum() + \
                ((lse * wgt_lse).sum() if partial else 0.0)
        return loss

    for got, want in zip(kernel(x, w, b)[:1 + partial], ref(x, w, b)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    g1 = jax.grad(weighted(kernel), argnums=(0, 1, 2))(x, w, b)
    g2 = jax.grad(weighted(ref), argnums=(0, 1, 2))(x, w, b)
    for a, c in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-4, atol=1e-4)


def test_fused_head_step_holds_two_kernels_and_no_logits(machine1,
                                                         pallas_kernels):
    """A model's loss and gradients through the fused head: the forward
    kernel and ONE backward kernel (dX, dW and db from each logits tile),
    no array of tokens x vocabulary outside them, and one count a trace
    of the tiles that ran."""
    from flexflow_tpu import obs
    from flexflow_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)

    vocab, tokens = 200, 8 * 256
    tcfg = TransformerConfig(batch_size=8, seq_length=256, num_layers=1,
                             d_model=16, num_heads=4, d_ff=32,
                             vocab_size=vocab, causal=True)
    toks = jnp.zeros((8, 256), "int32")
    counted = ("kernels.ce.fwd.1024x256", "kernels.ce.fused_bwd.1024x256")
    before = [obs.snapshot()["counters"].get(c, 0) for c in counted]
    with pallas_kernels():
        tlm = TransformerLM(tcfg, machine1)
        params, state = tlm.init(seed=0)
        closed = jax.make_jaxpr(jax.grad(
            lambda p: tlm.loss_fn(p, state, toks, toks, train=True)[0]))(
                params)
    after = [obs.snapshot()["counters"].get(c, 0) for c in counted]
    assert after == [n + 1 for n in before], (before, after)
    eqns = list(_top_level_eqns(closed.jaxpr))
    head = [e.params["name"] for e in eqns
            if e.primitive.name == "pallas_call"
            and e.params["name"].startswith("ff_ce_")]
    assert head == ["ff_ce_fwd", "ff_ce_bwd"], head
    for e in eqns:
        for var in e.outvars:
            shape = getattr(var.aval, "shape", ())
            assert not (any(s >= tokens for s in shape)
                        and any(vocab <= s < tokens for s in shape)), \
                (e.primitive.name, shape)


@pytest.mark.parametrize("d", [768, 2048, 4096])
def test_fused_ce_tiles_fit_the_vmem_budget(d):
    """The tile rule is a function of shapes and types: within the VMEM
    the kernels ask Mosaic for (64 MiB, as the flash and grouped-product
    kernels), whole lanes, a tile of at most 1024 x 1024 logits, the
    forward wide and the backward tall, and a backward sweep of the
    vocabulary of one block or at least four.
    (tests/test_kernels_compile_for_v5e.py hands the picks to Mosaic.)"""
    from flexflow_tpu.ops.pallas import fused_ce as ce
    assert ce._VMEM_LIMIT_BYTES == 64 * 1024 * 1024
    for n, v in ((16384, 50257), (16384, 100352), (16384, 20480),
                 (2048, 32000), (4096, 384), (2048, 200)):
        for itemsize in (2, 4):
            fwd = ce._pick_tiles(n, v, d, itemsize, backward=False)
            bwd = ce._pick_tiles(n, v, d, itemsize, backward=True)
            assert ce._fwd_bytes(*fwd, d, itemsize) <= ce._VMEM_LIMIT_BYTES
            assert ce._bwd_bytes(*bwd, d, itemsize) <= ce._VMEM_LIMIT_BYTES
            for bn, bv in (fwd, bwd):
                assert bn % 128 == 0 and bv % 128 == 0, (fwd, bwd)
                assert bn * bv <= ce._TILE_ELEMENTS
            sweep = -(-v // bwd[1])
            assert sweep == 1 or sweep >= ce._MIN_SWEEP, (v, bwd)
    # the three token cells (bfloat16, 16 384 tokens)
    cells = {768: ((512, 2048), (1024, 1024)),
             2048: ((512, 2048), (1024, 512)),
             4096: ((512, 2048), (512, 256))}
    assert (ce._pick_tiles(16384, 50257, d, 2, backward=False),
            ce._pick_tiles(16384, 50257, d, 2, backward=True)) == cells[d]


def test_fused_ce_refuses_a_compiled_sweep_of_two_or_three_blocks():
    """Forced tiles that would have the compiled backward fetch a block of
    dW whose write-back may still be in flight (read wrong on the chip at
    a sweep of two, PR 33) are refused while the call is traced; the
    interpreters, which have no write in flight, take them."""
    from flexflow_tpu.ops.pallas.fused_ce import fused_linear_ce

    x = jax.ShapeDtypeStruct((256, 128), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256,), jnp.float32)
    lab = jax.ShapeDtypeStruct((256,), jnp.int32)

    def grads(block_v, interpret):
        return jax.eval_shape(jax.grad(lambda x, w, b, lab: fused_linear_ce(
            x, w, b, lab, block_n=128, block_v=block_v,
            interpret=interpret).sum(), argnums=(0, 1, 2)), x, w, b, lab)

    with pytest.raises(ValueError, match="in flight"):
        grads(128, False)          # a sweep of two
    grads(256, False)              # one block: the sum stays resident
    grads(128, True)


@pytest.mark.parametrize("n,block_n", [
    (16384, 1024), (2048, 1024),
    (2100, 256),    # 1024 pads to 3072 and 512 to 2560: over a tenth
    (2400, 512),    # 1024 pads to 3072; 512 to 2560, 160 of 2400
    (5000, 1024),   # 5120: 120 rows of padding
    (100, 128),     # under a lane tile: one block of 128
])
def test_fused_ce_token_block_steps_down_when_padding_passes_a_tenth(
        n, block_n):
    from flexflow_tpu.ops.pallas import fused_ce as ce
    # the backward takes the tallest token block the axis allows; the
    # forward stops at the tile's 1024 x 1024 beside its 2048 columns
    assert ce._pick_tiles(n, 50257, 768, 2, backward=True)[0] == block_n
    assert ce._pick_tiles(n, 50257, 768, 2, backward=False)[0] == \
        min(block_n, 512)


def test_lm_head_fusion_matches_unfused(machine8, pallas_kernels):
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
    with pallas_kernels():
        fused = run()
    assert abs(base - fused) < 1e-3, (base, fused)


def test_lm_head_fusion_grads_match(machine8, pallas_kernels):
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
    with pallas_kernels():
        fused = grads()
    for a, c in zip(base, fused):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-3, atol=2e-3)


def test_lm_head_fusion_vocab_tp(machine8, pallas_kernels):
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
        ctx = pallas_kernels() if fused else contextlib.nullcontext()
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
# The rule for which kernel runs (PR 30): one gate that follows the
# backend, shapes beside each kernel, no switch.  A convolutional model
# takes no kernel on one device or on a grid.


def test_the_kernel_gate_follows_the_backend_and_no_environment(monkeypatch):
    import os

    from flexflow_tpu.ops import pallas

    assert not pallas.flash_enabled()            # this CPU mesh

    class Untouched(dict):
        def _read(self, *a):
            raise AssertionError(f"the kernel gate read the environment: {a}")

        get = __getitem__ = __contains__ = _read

    for backend in ("tpu", "cpu", "gpu"):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        with monkeypatch.context() as patch:
            patch.setattr(os, "environ", Untouched())
            assert pallas.flash_enabled() == (backend == "tpu")
    assert pallas.__all__ == ["KEPT_RESULTS", "flash_attention",
                              "flash_enabled"]


def _traced_cnn_step(build, machine, batch, size):
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.data.synthetic import _batch_sharding

    ff = build(FFConfig(batch_size=batch, input_height=size,
                        input_width=size, num_classes=16,
                        compute_dtype="bfloat16"), machine)
    sharding = _batch_sharding(machine)
    avals = (jax.ShapeDtypeStruct((batch, size, size, 3), jnp.float32,
                                  sharding=sharding),
             jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=sharding))
    return ff, ff.make_train_step().trace(*ff.abstract_train_state(),
                                          *avals)


def _kernel_calls(traced):
    """The names of a traced step's pallas_calls (in interpret mode a
    kernel lowers to plain loops, so the lowered text cannot say)."""
    return [e.params["name"] for e in _top_level_eqns(traced.jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"]


def _pool_instructions(traced):
    """The result types of the step's pooling instructions, in program
    order."""
    import re

    return re.findall(r'"stablehlo\.(?:reduce_window|select_and_scatter)"'
                      r'.*?\) -> (tensor<[^>]*>)', traced.lower().as_text(),
                      flags=re.S)


def test_alexnet_lowers_the_same_pools_on_one_device_and_on_four(
        pallas_kernels):
    """What a one-chip control cell needs: the routing may not depend on
    the device count.  Three max pools forward (``reduce_window``) and
    three backward (``select_and_scatter``) either way, and no kernel,
    with the kernel gate open as it is on a TPU."""
    from flexflow_tpu.machine import MachineModel
    from flexflow_tpu.models import build_alexnet

    with pallas_kernels():
        _, one = _traced_cnn_step(
            build_alexnet, MachineModel(jax.devices()[:1]), 8, 64)
        ff, four = _traced_cnn_step(
            build_alexnet, MachineModel(jax.devices()[:4]), 8, 64)
    assert all(len(op.pc.devices) == 4 for op in ff.layers)
    pools = _pool_instructions(one)
    assert len(pools) == 6 and pools == _pool_instructions(four)
    for traced in (one, four):
        assert _kernel_calls(traced) == []


def test_inception_lowers_no_kernel(pallas_kernels, machine1):
    from flexflow_tpu.models import build_inception_v3
    from flexflow_tpu.ops.pool import POOL_MAX, Pool2D

    with pallas_kernels():
        ff, traced = _traced_cnn_step(build_inception_v3, machine1, 2, 299)
    pools = [op.pool_type for op in ff.layers if isinstance(op, Pool2D)]
    assert len(pools) == 14 and pools.count(POOL_MAX) == 4
    assert _kernel_calls(traced) == []
    assert traced.lower().as_text().count(
        "stablehlo.select_and_scatter") == 4


def test_ffconfig_has_no_kernel_switch():
    import dataclasses

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import TransformerConfig
    from flexflow_tpu.nmt.rnn_model import RnnConfig

    for cls in (FFConfig, TransformerConfig, RnnConfig):
        assert "pallas" not in {f.name for f in dataclasses.fields(cls)}
    # an unknown flag and its value are passed over, as the reference
    # parser does; the flags around it still land
    cfg = FFConfig.from_args(["-b", "32", "--pallas", "off", "--lr", "0.5"])
    assert (cfg.batch_size, cfg.learning_rate) == (32, 0.5)
    assert not hasattr(cfg, "pallas")
    with pytest.raises(TypeError):
        FFConfig(pallas="off")
