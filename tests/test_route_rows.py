"""``ops/expert_share.route_rows``: where each (token, held expert) pair
goes in the held experts' buffer.  Its six outputs against a host
reference built on ``np.searchsorted`` (kept here, not in the program),
at the expert cells' shapes and at the corners of the mask; the blocked
count against the search alone; the operator's output and gradients
against the same operator on the search; each token's live slots, and
dispatch and combine over them against the same moves over every held
slot, bit for bit; no ``while`` in the lowered route; the counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import obs
from flexflow_tpu.ops import expert_share
from flexflow_tpu.ops.base import Tensor
from flexflow_tpu.strategy import ParallelConfig


def _reference(held_gates, rows_capacity, per_token):
    """route_rows on the host, row by row through ``np.searchsorted``;
    a token's live slots by a walk along its row."""
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
    k = min(per_token, e)
    if k == e:
        slot_live = slot_rows
    else:
        slot_live = np.full((t, k), rows_capacity, slot_rows.dtype)
        for i, row in enumerate(slot_rows):
            mine = row[row < rows_capacity]
            slot_live[i, :len(mine)] = mine
    return (row_token, row_w, slot_rows, slot_live, group_sizes,
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
    # the most pairs a token has here, as top_k bounds them in a layer
    per_token = max(1, int((g > 0).sum(1).max()))
    got = jax.jit(lambda g: expert_share.route_rows(g, rows, per_token))(g)
    want = _reference(g, rows, per_token)
    assert len(got) == len(want) == 6
    for name, a, b in zip(("row_token", "row_w", "slot_rows", "slot_live",
                           "group_sizes", "dropped"), got, want):
        np.testing.assert_array_equal(np.asarray(a), b, err_msg=name)
    if kind == "every" and rows < t * e:
        assert int(got[5]) == t * e - rows


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
    text = jax.jit(lambda g: expert_share.route_rows(g, 32768, 4)).lower(
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
    jax.jit(lambda g: expert_share.route_rows(g, 24576, 6)).lower(
        jax.ShapeDtypeStruct((16384, 8), jnp.float32))
    assert obs.snapshot()["counters"].get(name, 0) == before + 1


# ---------------------------------------------------------------------------
# a token's live slots: combine and dispatch's gradient gather
# min(top_k, experts held) rows a token, not one for every held expert


def _top_k_gates(t, n_router, top_k, seed, zero_gate=False):
    """(tokens, router outputs): ``top_k`` experts a token at random, each
    weighted by a power of two; with ``zero_gate`` every third token's
    first choice has a weight of exactly 0, so it is selected and is no
    pair.  A product with a power of two is exact, so a compiler that
    fuses a weighted term's product into its sum (the CPU's LLVM does,
    where it likes) rounds it as one that does not: what is left to
    compare is which terms are summed, and in which order."""
    rng = np.random.RandomState(seed)
    top = np.argsort(rng.rand(t, n_router), -1)[:, :top_k]
    gates = np.zeros((t, n_router), np.float32)
    np.put_along_axis(gates, top, 2.0 ** -rng.randint(0, 6, (t, top_k)),
                      -1)
    if zero_gate:
        gates[np.arange(0, t, 3), top[::3, 0]] = 0.0
    return gates


def _capacity(t, n_router, held, top_k, factor):
    """``HeldExperts.rows_capacity``'s rule."""
    return min(t * min(top_k, held),
               8 * int(np.ceil(factor * t * top_k * held / n_router / 8)))


# (router outputs, experts held, top_k, capacity factor): the cells'
# held/top_k (smallthinker_21b_a3b 16/6, lfm2_8b_a1b 8/4,
# moonlight_16b_a3b 8/6, and laguna_s_2_1's 8 under top-10, where every
# held slot may be live and the moves keep their full width), then a
# buffer that drops pairs
LIVE_CASES = [(64, 16, 6, 2.0), (32, 8, 4, 2.0), (64, 8, 6, 2.0),
              (256, 8, 10, 2.0), (64, 16, 6, 0.25)]


@pytest.mark.parametrize("n_router,held,top_k,factor", LIVE_CASES)
def test_live_slots_are_each_tokens_rows_in_expert_order(
        n_router, held, top_k, factor):
    t = 16384
    g = _top_k_gates(t, n_router, top_k, 41)[:, :held]
    rows = _capacity(t, n_router, held, top_k, factor)
    k = min(top_k, held)
    name = f"moe.route.slots.{k}of{held}"
    before = obs.snapshot()["counters"].get(name, 0)
    got = jax.jit(lambda g: expert_share.route_rows(g, rows, top_k))(g)
    assert obs.snapshot()["counters"].get(name, 0) == before + 1
    slot_rows, slot_live = (np.asarray(a) for a in got[2:4])
    assert slot_live.shape == (t, k)
    np.testing.assert_array_equal(slot_live, _reference(g, rows, top_k)[3])
    assert (int(got[5]) > 0) == (factor < 1)
    if k < held:
        # the same rows, each token's once, the zero row after them
        for a, b in zip(slot_live, slot_rows):
            assert sorted(a[a < rows]) == sorted(b[b < rows])
        assert (np.diff(slot_live, axis=1) > 0)[slot_live[:, 1:] < rows].all()


def _moves(x, y, w, route, live):
    dispatch, combine = expert_share._row_moves()
    row_token, row_w, slot_rows, slot_live = route[:4]
    return (dispatch(x, row_token, slot_live if live else slot_rows),
            combine(y, w, row_token, row_w, slot_rows,
                    slot_live if live else None))


@pytest.mark.parametrize("zero_gate", [False, True])
@pytest.mark.parametrize("n_router,held,top_k,factor", LIVE_CASES)
def test_moves_over_live_slots_equal_every_held_slot_bit_for_bit(
        n_router, held, top_k, factor, zero_gate):
    """Dispatch's rows and its gradient (the token's rows summed), combine's
    output and both its gradients (rows, weights at full width), over the
    live slots against the parent's moves over every held slot."""
    t, d = 2048, 64
    gates = jnp.asarray(_top_k_gates(t, n_router, top_k, 7, zero_gate)
                        [:, :held])
    rows = _capacity(t, n_router, held, top_k, factor)
    route = jax.jit(lambda g: expert_share.route_rows(g, rows, top_k))(gates)
    if zero_gate:       # selected, weighted 0: no pair, no slot
        assert int(jnp.sum(gates > 0)) < t * top_k * held // n_router
    ks = jax.random.split(jax.random.PRNGKey(41), 4)
    x = jax.random.normal(ks[0], (t, d)).astype(jnp.bfloat16)
    y = jax.random.normal(ks[1], (rows, d)).astype(jnp.bfloat16)
    d_rows = jax.random.normal(ks[2], (rows, d)).astype(jnp.bfloat16)
    d_out = jax.random.normal(ks[3], (t, d)).astype(jnp.bfloat16)

    def run(live):
        outs, vjp = jax.vjp(lambda x, y, w: _moves(x, y, w, route, live),
                            x, y, gates)
        return outs, vjp((d_rows, d_out))

    got, want = jax.jit(lambda: run(True))(), jax.jit(lambda: run(False))()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.abs(got[0][1].astype(jnp.float32)).sum()) > 0


@pytest.mark.parametrize("n_router,held,top_k,factor", LIVE_CASES)
def test_held_experts_over_live_slots_equal_the_full_width(
        n_router, held, top_k, factor, monkeypatch):
    """The operator's output, state and every gradient, with the moves
    over a token's live slots and with the parent's over every held one
    (``route_rows`` told that every held slot may be live)."""
    tokens, d, f = (2, 24), 16, 24
    op = expert_share.HeldExperts(
        "e", ParallelConfig((1, 1), (0,)), Tensor(tokens + (d,)),
        Tensor(tokens + (n_router,)), f, (0, held), top_k, factor)
    p = op.init_params(jax.random.PRNGKey(3))
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(*tokens, d), jnp.float32)
    gates = jnp.asarray(_top_k_gates(48, n_router, top_k, 9, True)
                        .reshape(tokens + (n_router,)))
    w = jnp.asarray(rng.randn(*tokens, d), jnp.float32)

    def run():
        def loss(p, x, gates):
            y, st = op.forward(p, op.init_state(), [x, gates], True)
            return (y * w).sum(), (y, st)
        return jax.jit(jax.grad(loss, (0, 1, 2), has_aux=True))(p, x, gates)

    got = run()
    whole = expert_share.route_rows
    monkeypatch.setattr(expert_share, "route_rows",
                        lambda g, rows, k: whole(g, rows, g.shape[1]))
    want = run()
    assert (float(want[1][1]["dropped"]) > 0) == (factor < 1)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
