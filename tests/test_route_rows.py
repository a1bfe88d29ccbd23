"""``ops/expert_share.route_rows``: where each (token, held expert) pair
goes in the held experts' buffer.  Its five outputs against a host
reference built on ``np.searchsorted`` (kept here, not in the program),
at the three expert cells' shapes and at the corners of the mask; the
blocked count against the search alone; the operator's output and
gradients against the same operator on the search; no ``while`` in the
lowered route; the counter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import obs
from flexflow_tpu.ops import expert_share
from flexflow_tpu.ops.base import Tensor
from flexflow_tpu.strategy import ParallelConfig


def _reference(held_gates, rows_capacity):
    """route_rows on the host, row by row through ``np.searchsorted``."""
    g = np.asarray(held_gates)
    t, e = g.shape
    picked = (g > 0).T.reshape(-1)
    upto = np.cumsum(picked, dtype=np.int64)
    total = int(upto[-1])
    fits = picked & (upto <= rows_capacity)
    slot_rows = np.where(fits, upto - 1, rows_capacity).reshape(e, t).T
    ends = np.minimum(upto.reshape(e, t)[:, -1], rows_capacity)
    group_sizes = np.diff(ends, prepend=0)
    pair = np.searchsorted(upto, np.arange(1, rows_capacity + 1))
    used = np.arange(rows_capacity) < min(total, rows_capacity)
    pair = np.where(used, pair, 0)
    row_token, row_expert = pair % t, pair // t
    row_w = np.where(used, row_token * e + row_expert, -1)
    return (row_token, row_w, slot_rows, group_sizes,
            max(total - rows_capacity, 0))


def _mask(kind, t, e, load, seed=39):
    rng = np.random.RandomState(seed)
    if kind == "random":
        return (rng.rand(t, e) < load).astype(np.float32)
    if kind == "skewed":        # most pairs on the first held expert
        p = np.full(e, load / 4)
        p[0] = min(1.0, 4 * load)
        return (rng.rand(t, e) < p).astype(np.float32) * rng.rand(t, e)
    if kind == "none":
        return np.zeros((t, e), np.float32)
    return np.ones((t, e), np.float32)          # "every"


# (mask, tokens, experts held, rows_capacity, load): the three cells at
# their balanced load (moonlight_16b_a3b 6 of 64, lfm2_8b_a1b 4 of 32,
# laguna_s_2_1 10 of 256), then the corners
CASES = [
    ("random", 16384, 8, 24576, 6 / 64),
    ("random", 16384, 8, 32768, 4 / 32),
    ("random", 16384, 8, 10240, 10 / 256),
    ("skewed", 16384, 8, 24576, 6 / 64),
    ("none", 16384, 8, 24576, 0.0),
    ("every", 512, 8, 4096, 1.0),       # every pair, exactly the buffer
    ("every", 512, 8, 1000, 1.0),       # more pairs than rows: dropped
    ("random", 16384, 8, 4096, 6 / 64),  # twice the buffer: dropped
    ("random", 1000, 3, 600, 0.3),      # 3000 pairs: no block divides
    ("skewed", 77, 5, 64, 0.4),
]


@pytest.mark.parametrize("kind,t,e,rows,load", CASES)
def test_route_rows_equals_the_host_search(kind, t, e, rows, load):
    g = _mask(kind, t, e, load)
    got = jax.jit(lambda g: expert_share.route_rows(g, rows))(g)
    want = _reference(g, rows)
    for name, a, b in zip(("row_token", "row_w", "slot_rows",
                           "group_sizes", "dropped"), got, want):
        np.testing.assert_array_equal(np.asarray(a), b, err_msg=name)
    if kind == "every" and rows < t * e:
        assert int(got[4]) == t * e - rows


@pytest.mark.parametrize("block", [128, 256, 512, 1024, 2048])
@pytest.mark.parametrize("pairs,rows", [(131072, 32768), (3000, 1500),
                                        (200, 500)])
def test_first_reaching_is_searchsorted(block, pairs, rows):
    """Every row, used or not: past the last count the index is
    ``len(upto)``, as the search gives it."""
    rng = np.random.RandomState(pairs + block)
    upto = np.cumsum(rng.rand(pairs) < 0.3, dtype=np.int32)
    got = jax.jit(expert_share.first_reaching, static_argnums=(1, 2))(
        jnp.asarray(upto), rows, block)
    np.testing.assert_array_equal(
        np.asarray(got), np.searchsorted(upto, np.arange(1, rows + 1)))


def test_route_block_is_the_largest_power_of_two_under_the_root():
    assert expert_share.route_block(131072) == 256
    assert expert_share.route_block(4 ** 10) == 1024
    assert expert_share.route_block(4 ** 10 - 1) == 512
    assert expert_share.route_block(3000) == 128
    assert expert_share.route_block(1) == 128


def test_the_lowered_route_holds_no_while():
    g = jax.ShapeDtypeStruct((16384, 8), jnp.float32)
    text = jax.jit(lambda g: expert_share.route_rows(g, 32768)).lower(
        g).as_text()
    assert "while" not in text
    # the search it replaces is a loop: the check can see one
    search = jax.jit(lambda u: jnp.searchsorted(
        u, jnp.arange(1, 32769, dtype=jnp.int32))).lower(
        jax.ShapeDtypeStruct((131072,), jnp.int32)).as_text()
    assert "while" in search


def _searched(upto, rows, block):
    return jnp.searchsorted(upto, jnp.arange(1, rows + 1, dtype=jnp.int32))


@pytest.mark.parametrize("factor", [2.0, 0.5])
def test_held_experts_bit_equal_to_the_search(factor, monkeypatch):
    """The operator's output, state and every gradient, on the blocked
    count and on the parent's search, at a buffer that holds every pair
    and at one that drops some."""
    tokens, d, f, n_router, top_k = (2, 24), 16, 24, 8, 3
    op = expert_share.HeldExperts(
        "e", ParallelConfig((1, 1), (0,)), Tensor(tokens + (d,)),
        Tensor(tokens + (n_router,)), f, (1, 6), top_k, factor)
    p = op.init_params(jax.random.PRNGKey(3))
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(*tokens, d), jnp.float32)
    logits = rng.randn(*tokens, n_router)
    top = np.argsort(-logits, -1)[..., :top_k]
    gates = jnp.asarray(np.where(
        np.any(np.arange(n_router) == top[..., None], -2),
        np.abs(logits), 0.0), jnp.float32)
    w = jnp.asarray(rng.randn(*tokens, d), jnp.float32)

    def run():
        def loss(p, x, gates):
            y, st = op.forward(p, op.init_state(), [x, gates], True)
            return (y * w).sum(), (y, st)
        return jax.jit(jax.grad(loss, (0, 1, 2), has_aux=True))(p, x, gates)

    got = run()
    monkeypatch.setattr(expert_share, "first_reaching", _searched)
    want = run()
    assert (float(want[1][1]["dropped"]) > 0) == (factor < 1)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_traced_route_counts_its_blocks_once():
    name = "moe.route.blocked.512x256"
    before = obs.snapshot()["counters"].get(name, 0)
    jax.jit(lambda g: expert_share.route_rows(g, 24576)).lower(
        jax.ShapeDtypeStruct((16384, 8), jnp.float32))
    assert obs.snapshot()["counters"].get(name, 0) == before + 1
