"""The grouped products of a held-expert layer as Pallas kernels
(``ops/pallas/grouped_mm.py``), in interpret mode: each form against
``jax.lax.ragged_dot`` and against a numpy loop over the groups, the
rule that picks their tiles, and ``HeldExperts`` with the kernel gate
open against the gate shut."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops.base import Tensor
from flexflow_tpu.strategy import ParallelConfig

gm = importlib.import_module("flexflow_tpu.ops.pallas.grouped_mm")

# (rows, groups' sizes): the tiles below cut the buffer in rows of 32
_GROUPS = {
    "uneven": (128, [40, 17, 50, 21]),            # every boundary in a tile
    "an_empty_group": (128, [40, 0, 56, 32]),
    "empty_first_and_last": (128, [0, 64, 64, 0]),
    "on_tile_edges": (128, [32, 64, 0, 32]),
    "below_the_buffer": (160, [33, 20, 0, 11]),   # 64 rows, then 3 dead tiles
    "one_row": (96, [0, 1, 0, 0]),
    "nothing_routed": (64, [0, 0, 0, 0]),
}
_TILES = {"whole": (32, 0, 0), "depth_steps": (32, 128, 0),
          "column_tiles": (32, 0, 128), "short_last_column": (32, 0, 256),
          "rows_of_16": (16, 0, 0)}


@pytest.fixture(autouse=True)
def pieces_of_8(monkeypatch):
    """A row tile that a boundary cuts is walked in pieces of 8 rows (128
    on the chip), so the tiles of 32 and 16 here have pieces to skip."""
    monkeypatch.setattr(gm, "_SUB_ROWS", 8)
    gm._make_gated_ffn.cache_clear()
    yield
    gm._make_gated_ffn.cache_clear()


def _loop(form, a, other, sizes):
    """The product group by group, in float64."""
    a, other = np.asarray(a, np.float64), np.asarray(other, np.float64)
    starts = np.concatenate([[0], np.cumsum(sizes)])
    if form == "dw":
        return np.stack([a[lo:hi].T @ other[lo:hi]
                         for lo, hi in zip(starts, starts[1:])])
    out = np.zeros((a.shape[0], other.shape[1 if form == "gmm_t" else 2]))
    for g, (lo, hi) in enumerate(zip(starts, starts[1:])):
        out[lo:hi] = a[lo:hi] @ (other[g].T if form == "gmm_t" else other[g])
    return out


def _ragged(form, a, other, sizes):
    """What autodiff of ``ragged_dot`` computes in the form's place."""
    f32 = jnp.float32

    def dot(a_, w_):
        return jax.lax.ragged_dot(a_, w_, sizes, preferred_element_type=f32)

    if form == "gmm":
        return dot(a, other)
    if form == "gmm_t":
        # d a of dot(a, w) with w (G, n, c): the cotangent is this ``a``
        primal = jnp.zeros((a.shape[0], other.shape[1]), a.dtype)
        return jax.vjp(lambda x: dot(x, other), primal)[1](a.astype(f32))[0]
    primal = jnp.zeros((sizes.shape[0], a.shape[1], other.shape[1]),
                       a.dtype)
    return jax.vjp(lambda w: dot(a, w), primal)[1](other.astype(f32))[0]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("groups", sorted(_GROUPS))
@pytest.mark.parametrize("form", ["gmm", "gmm_t", "dw"])
def test_each_form_against_ragged_dot_and_a_loop(form, groups, dtype):
    """Rows past the last group hold NaN on the way in: they reach no
    result, and the row forms read exactly 0 there."""
    m, sizes = _GROUPS[groups]
    c, n, live = 256, 384, sum(sizes)
    rng = np.random.RandomState(len(groups))
    a = jnp.asarray(rng.randn(m, c), dtype)
    other = jnp.asarray(rng.randn(*{
        "gmm": (4, c, n), "gmm_t": (4, n, c), "dw": (m, n)}[form]), dtype)
    want = _loop(form, a, other, sizes)
    dirty = a.at[live:].set(jnp.nan)
    dirty_other = other.at[live:].set(jnp.nan) if form == "dw" else other
    sizes = jnp.asarray(sizes, jnp.int32)
    call = {"gmm": gm.ff_gmm, "gmm_t": gm.ff_gmm_t, "dw": gm.ff_gmm_dw}[form]
    tol = dict(rtol=2e-2, atol=0.3) if dtype == "bfloat16" \
        else dict(rtol=1e-4, atol=1e-3)
    ragged = _ragged(form, a, other, sizes)
    np.testing.assert_allclose(np.asarray(ragged, np.float32), want, **tol)
    # float32 sums rounded once to the operands' type, as autodiff
    # rounds ragged_dot's: equal to one unit in the last place
    ragged = np.asarray(ragged.astype(dtype), np.float32)
    # every tiling on the two walks that have it all (cut tiles, an empty
    # group, dead tiles); the others at two row tiles
    tilings = _TILES if groups in ("uneven", "below_the_buffer") \
        else ("whole", "rows_of_16")
    for name in tilings:
        tm, tk, tn = _TILES[name]
        got = call(dirty, dirty_other, sizes,
                   tiles=(tm, tk or c, tn or n))
        assert got.dtype == a.dtype and got.shape == want.shape, name
        got = np.asarray(got, np.float32)
        assert np.isfinite(got).all(), name
        np.testing.assert_allclose(got, want, err_msg=name, **tol)
        np.testing.assert_allclose(got, ragged, err_msg=name, **(
            dict(rtol=2 ** -7, atol=1e-2) if dtype == "bfloat16" else tol))
        if form != "dw":
            assert not got[live:].any(), name


def test_a_result_leaves_the_kernel_in_the_type_asked_for():
    a = jnp.ones((64, 128), jnp.bfloat16)
    w = jnp.ones((2, 128, 128), jnp.bfloat16)
    sizes = jnp.asarray([30, 20], jnp.int32)
    out = gm.ff_gmm(a, w, sizes, jnp.float32, tiles=(32, 128, 128))
    assert out.dtype == jnp.float32
    np.testing.assert_array_equal(out[:50], 128.0)
    np.testing.assert_array_equal(out[50:], 0.0)
    with pytest.raises(ValueError, match="no tiles"):
        gm.ff_gmm(a[:, :100], w[:, :100], sizes)


def test_the_walk_visits_every_tile_once_a_group():
    sizes = jnp.asarray([33, 20, 0, 11], jnp.int32)
    offsets, gids, tiles, counts = (np.asarray(v) for v in gm._visits(
        sizes, 160, 32, False))
    assert offsets.tolist() == [0, 33, 53, 53, 64]
    live, total = counts.tolist()
    # group 0 in tiles 0-1, group 1 in tile 1, group 3 in tile 1;
    # then tiles 2, 3, 4 to fill with zeros; the rest repeats
    assert (live, total) == (4, 7) and len(gids) == 5 + 4 - 1
    assert gids[:live].tolist() == [0, 0, 1, 3]
    assert tiles.tolist() == [0, 1, 1, 1, 2, 3, 4, 4]
    offsets, gids, tiles, counts = (np.asarray(v) for v in gm._visits(
        sizes, 160, 32, True))
    assert counts.tolist() == [5, 5]             # the empty group once
    assert gids[:5].tolist() == [0, 0, 1, 2, 3]
    assert tiles[:5].tolist() == [0, 1, 1, 1, 1]


def test_tiles_by_shapes():
    """The rule beside the kernels, as a table: a pure function of shapes
    and types."""
    pick = gm._pick_tiles
    # the Moonlight cell: 24 576 buffer rows, 2048 x 1408, bfloat16; the
    # whole depth and the 1408 columns whole, not padded to 1536; the
    # weight gradients at the smallest row tile
    assert pick("gmm", 24576, 2048, 1408, 2, 4) == (512, 2048, 1408)
    assert pick("gmm", 24576, 1408, 2048, 2, 2) == (512, 1408, 2048)
    assert pick("gmm_t", 24576, 2048, 1408, 2, 2) == (512, 2048, 1408)
    assert pick("gmm_t", 24576, 1408, 2048, 2, 2) == (512, 1408, 2048)
    assert pick("dw", 24576, 2048, 1408, 2, 2) == (128, 2048, 1408)
    assert pick("dw", 24576, 1408, 2048, 2, 2) == (128, 1408, 2048)
    # a buffer only the smaller row tiles divide
    assert pick("gmm", 768, 256, 128, 2, 2) == (256, 256, 128)
    assert pick("gmm_t", 384, 256, 128, 2, 2) == (128, 256, 128)
    # DeepSeek-V3's own experts (7168 x 2048) pass the budget whole: the
    # depth stays whole and the columns split, the last tile short
    assert pick("gmm", 8192, 7168, 2048, 2, 2) == (512, 7168, 768)
    assert pick("gmm_t", 8192, 2048, 7168, 2, 2) == (512, 2048, 2432)
    assert pick("dw", 8192, 7168, 2048, 2, 2) == (128, 1792, 2048)
    # float32 operands at the cell's shape still pass whole; at twice the
    # depth the columns go in two
    assert pick("gmm", 24576, 2048, 1408, 4, 4) == (512, 2048, 1408)
    assert pick("gmm", 24576, 4096, 1408, 4, 4) == (512, 4096, 512)
    # not the kernels' shapes: the caller keeps ragged_dot
    assert pick("gmm", 24576, 2048, 1400, 2, 2) is None     # no lane multiple
    assert pick("gmm", 24576, 100, 1408, 2, 2) is None
    assert pick("gmm", 96, 128, 128, 4, 4) is None          # no row tile
    assert gm._make_gated_ffn(96, 64, 32, 4, "float32", True) is None


# ---------------------------------------------------------------------------
# HeldExperts through the kernels


@pytest.fixture
def small_rows(monkeypatch):
    """Row tiles cut to test sizes, so that a buffer of 48 rows is the
    kernels' and interpret mode walks boundaries as the chip does at 512."""
    monkeypatch.setattr(gm, "_ROW_TILES", (32, 16))
    yield


def _experts(d, f, tokens=(2, 12), act="silu"):
    from flexflow_tpu.ops.expert_share import HeldExperts

    return HeldExperts("e", ParallelConfig((1, 1), (0,)),
                       Tensor(tokens + (d,)), Tensor(tokens + (8,)), f,
                       (2, 6), 2, act=act)


@pytest.mark.parametrize("act", ["silu", "relu"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_held_experts_with_the_gate_open_against_the_gate_shut(
        dtype, act, small_rows, pallas_kernels):
    """Values and all gradients (rows, router weights, the three expert
    matrices), and the counter that says which path a layer took, under
    either gate of the experts (SwiGLU, ReGLU)."""
    from flexflow_tpu import obs

    op = _experts(128, 256, act=act)
    assert op.rows_capacity == 48
    p = op.init_params(jax.random.PRNGKey(5))
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 12, 128), dtype)
    w = jnp.asarray(rng.randn(2, 12, 128), jnp.float32)
    # two of eight experts a token, a third of them held here; expert 4
    # gets no token
    scores = rng.rand(2, 12, 8)
    scores[..., 4] = 0.0
    top = np.sort(scores, -1)[..., -2:-1]
    gates = jnp.asarray(np.where(scores >= top, scores, 0.0), jnp.float32)

    def loss(p, x, gates):
        y, _ = op.forward(p, op.init_state(), [x, gates], True)
        return (y.astype(jnp.float32) * w).sum(), y

    def run():
        before = dict(obs.snapshot()["counters"])
        out = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(p, x, gates)
        after = obs.snapshot()["counters"]
        return out, {k: v - before.get(k, 0) for k, v in after.items()
                     if k.startswith("kernels.gmm.")
                     and v != before.get(k, 0)}

    want, counted = run()
    assert counted == {"kernels.gmm.ragged_dot": 1}
    with pallas_kernels():
        got, counted = run()
    assert counted == {"kernels.gmm.ff_gmm.16x128x256": 1}
    assert got[0][1].dtype == x.dtype
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == "bfloat16" \
        else dict(rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and np.isfinite(np.asarray(
            a, np.float32)).all()
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)
    # no token on expert 4: its three gradients are exactly 0
    for leaf in jax.tree.leaves(got[1][0]):
        assert not np.asarray(leaf[2]).any()


def test_shapes_that_are_not_the_kernels_keep_ragged_dot(pallas_kernels):
    """The gate open on the tiny preset's widths (16 and 24: no lane
    multiple): the layer counts ``kernels.gmm.ragged_dot`` as before."""
    from flexflow_tpu import obs

    op = _experts(16, 24)
    p = op.init_params(jax.random.PRNGKey(1))
    x = jnp.ones((2, 12, 16))
    gates = jnp.zeros((2, 12, 8)).at[..., 3].set(1.0)
    before = obs.snapshot()["counters"].get("kernels.gmm.ragged_dot", 0)
    with pallas_kernels():
        y, _ = op.forward(p, op.init_state(), [x, gates], True)
    assert obs.snapshot()["counters"]["kernels.gmm.ragged_dot"] == before + 1
    assert np.isfinite(np.asarray(y)).all()


def test_a_nan_past_the_last_group_reaches_no_gradient(small_rows,
                                                       pallas_kernels):
    """``combine_bwd`` multiplies the rows of no pair by a zero weight,
    which a NaN would survive: the kernels write zeros there, in both
    passes, whatever the buffer held."""
    sizes = jnp.asarray([10, 0, 7, 3], jnp.int32)
    rng = np.random.RandomState(0)
    rows = jnp.asarray(rng.randn(48, 128), jnp.float32).at[20:].set(jnp.nan)
    ws = [jnp.asarray(rng.randn(*s) * 0.1, jnp.float32)
          for s in ((4, 128, 128), (4, 128, 128), (4, 128, 128))]
    d_y = jnp.asarray(rng.randn(48, 128), jnp.float32).at[20:].set(jnp.nan)
    y, tiles = gm.gated_ffn(rows, sizes, *ws)
    assert tiles == (16, 128, 128)
    assert not np.asarray(y[20:]).any()
    _, pull = jax.vjp(lambda r, *w: gm.gated_ffn(r, sizes, *w)[0], rows, *ws)
    for grad in pull(d_y):
        assert np.isfinite(np.asarray(grad)).all()
    assert not np.asarray(pull(d_y)[0][20:]).any()
