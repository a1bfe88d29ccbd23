"""Grouped matrix products over ragged row groups as Pallas TPU kernels:
the nine products of a held-expert layer (``ops/expert_share.py``).

A buffer of ``m`` rows holds the rows of ``G`` groups one after the other
(``group_sizes``; rows past their sum belong to no group).  Three forms,
all with float32 accumulation and the result cast once, in the kernel, to
the type the next operation reads:

  * ``ff_gmm``: the rows of each group times that group's matrix,
    ``a (m, c) x w (G, c, n) -> (m, n)`` (gate, up and down forward);
  * ``ff_gmm_t``: the same against the transposed matrix,
    ``a (m, c) x w (G, n, c)^T -> (m, n)`` (the rows' gradients), with no
    transposed copy of ``w`` in HBM;
  * ``ff_gmm_dw``: per group, rows transposed times rows,
    ``a (m, k)^T x b (m, n) -> (G, k, n)`` (the weight gradients).

``jax.lax.ragged_dot`` computes the same on the groups' rows; XLA's TPU
backend compiles it to a grouped Mosaic matmul of its own at a tiling of
512 x 512 x 128, which at a held expert's shapes (24 576 rows, 2048 x
1408) is some 1500 grid steps of 0.34 us of MXU work each and takes
0.94-1.02 ms a forward product that needs 0.36 ms at peak (PERF.md
section 5).  Here the tiles are a pure
function of the shapes (:func:`_pick_tiles`): the whole depth and the
whole width of a group's matrix where they fit VMEM, so that a matrix is
fetched once a group and a product is some tens of grid steps.

Walk.  The group offsets and the list of visits reach the kernel by scalar
prefetch (:func:`_visits`).  A visit is one (row tile, group) pair: a tile
that lies inside one group is visited once, a tile that a group boundary
cuts once a group, one visit after the other.  The row forms walk a tile
in pieces of 128 rows: the first visit of a tile clears it, and a visit
computes the pieces that hold rows of its group and keeps, of those, its
group's rows, so a boundary costs a piece, not a tile.  The visits past
the last group's name each remaining tile once and the kernel clears it
without fetching anything (``ragged_dot`` on the TPU leaves those rows as
they were; ``expert_share.combine_bwd`` multiplies them by a zero weight,
which a NaN would survive); what is left of the static grid does nothing.
``ff_gmm_dw`` accumulates a group's visits in VMEM and writes the group's
matrix once; an empty group is visited once and reads zeros; on a tile
that a boundary cuts both of its operands are masked (a row of another
group, or of none, may hold anything).

:func:`gated_ffn` puts the nine products of a gated feed-forward (a SiLU
or ReLU gate) under one ``custom_vjp``; every rounding is where autodiff
of ``ragged_dot`` has it: float32 ``gate``, ``up`` and activation, one
rounding of ``h`` and of ``y``, row gradients rounded once a product,
weight gradients accumulated in float32 and rounded once.

Measured on a v5e (PERF.md section 6, PR 31; 24 576 buffer rows, 8 groups
that fill half of it, 2048 x 1408, bfloat16, ms a product): ``ff_gmm``
0.54-0.57, ``ff_gmm_t`` 0.51-0.54, ``ff_gmm_dw`` 0.53, against 1.54-2.99
for the call autodiff of ``ragged_dot`` makes in each one's place; the
gated feed-forward with its backward 6.9 against 18.1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu import obs
from flexflow_tpu.ops.pallas import traced_once

LANES = 128
_F32 = jnp.float32
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# what the blocks of one call may hold, double-buffered, beside Mosaic's
# own temporaries
_VMEM_BUDGET_BYTES = 40 * 1024 * 1024
# row tiles, and the pieces of a tile the row forms walk.  A tile that a
# group boundary cuts is visited once a group.  The row forms compute only
# the pieces that hold rows of the visit's group, so they take the largest
# tile that divides the buffer (fewer grid steps and fetches);
# ff_gmm_dw computes a cut tile whole and masked once a group, so it
# takes the smallest.  PERF.md section 6, PR 31, ms a product at the
# Moonlight cell's shape: row forms 0.575 / 0.586 at 512 / 256 rows in
# pieces of 128 (0.641 / 0.603 with the tile one piece); ff_gmm_dw 0.564 /
# 0.532 / 0.537 at 512 / 256 / 128, and at 128 its two copies of the
# product are half the code of 256
_ROW_TILES = (512, 256, 128)
_SUB_ROWS = 128


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def _round_up(x: int, m: int) -> int:
    return _cdiv(x, m) * m


def _splits(x: int, even: bool):
    """Tile sizes along a dimension of x, the whole first: then x in 2, 3,
    4, 6 and 8 parts of whole lanes, the last of which may be short
    unless ``even``."""
    out = [x]
    for parts in (2, 3, 4, 6, 8):
        t = _round_up(_cdiv(x, parts), LANES)
        if t < x and t not in out and not (even and x % t):
            out.append(t)
    return out


def _pick_tiles(form: str, m: int, k: int, n: int, itemsize: int,
                out_itemsize: int):
    """(row tile, second tile, third tile) of one product, or None where
    the shapes are not the kernels' (widths that are no lane multiple, a
    buffer that no row tile divides): a pure function of shapes and types.

    ``gmm`` / ``gmm_t``: a (m, k), w (G, k, n) or (G, n, k), result (m, n);
    the tiles are (rows, depth, columns).  The depth is kept whole before
    the columns are, since a depth step costs a pass over the float32
    accumulator; a short last column tile is fine, a short depth tile
    would add what lies beyond the matrix.  ``dw``: a (m, k), b (m, n),
    result (G, k, n); the tiles are (rows a step, k, n) and the float32
    accumulator is the (k, n) block."""
    rows = _ROW_TILES[::-1] if form == "dw" else _ROW_TILES
    tm = next((t for t in rows if m % t == 0), None)
    if tm is None or k % LANES or n % LANES:
        return None
    if form == "dw":
        def held(tk, tn):
            return (2 * tm * (tk + tn) * itemsize
                    + tk * tn * (4 + 2 * out_itemsize))
        choices = [(tk, tn) for tn in _splits(n, False)
                   for tk in _splits(k, False)]
    else:
        def held(tk, tn):
            return (2 * (tm * tk + tk * tn) * itemsize
                    + tm * tn * (4 + 2 * out_itemsize))
        choices = [(tk, tn) for tk in _splits(k, True)
                   for tn in _splits(n, False)]
    for tk, tn in choices:
        if held(tk, tn) <= _VMEM_BUDGET_BYTES:
            return tm, tk, tn
    return None


def _visits(group_sizes, m: int, tm: int, empty_groups: bool):
    """The walk over a buffer of ``m`` rows in tiles of ``tm``, as four
    int32 arrays for scalar prefetch: ``offsets`` (G + 1; group g holds
    rows offsets[g]:offsets[g+1]), ``gids`` and ``tiles`` (the group and
    the row tile of each of the m/tm + G - 1 visits a grid holds) and
    ``counts`` = (live, total): visits below ``live`` compute, in group
    order; those from there to ``total`` name the tiles no group reaches,
    each once; the rest repeat the last.  With ``empty_groups`` a group
    without rows gets one visit (``ff_gmm_dw`` must still write its
    matrix) and ``total`` is ``live``."""
    g = group_sizes.shape[0]
    tiles_m = m // tm
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tm, tiles_m - 1)
    per_group = jnp.where(sizes > 0, (ends - 1) // tm - first + 1,
                          1 if empty_groups else 0)
    upto = jnp.cumsum(per_group)
    live = upto[-1]
    i = jnp.arange(tiles_m + g - 1, dtype=jnp.int32)
    at = jnp.minimum(i, jnp.maximum(live - 1, 0))
    # the group of visit ``at``: as many groups end their visits at or
    # before it; then that group's first tile and first visit, by a sum
    # over the few groups (cheaper to trace than a search and gathers)
    gids = jnp.minimum(jnp.sum(at[:, None] >= upto[None, :], axis=1), g - 1)
    here = gids[:, None] == jnp.arange(g, dtype=jnp.int32)[None, :]
    tiles = at + jnp.sum(jnp.where(here, (first - upto + per_group)[None, :],
                                   0), axis=1)
    if empty_groups:
        total = live
    else:
        reached = (ends[-1] + tm - 1) // tm
        tiles = jnp.where(i < live, tiles,
                          jnp.minimum(reached + i - live, tiles_m - 1))
        total = live + tiles_m - reached
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets, gids.astype(jnp.int32), tiles.astype(jnp.int32),
            jnp.stack([live, total]).astype(jnp.int32))


def _params(interpret):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)}


def _rows_of(offsets, gids, tiles, i, tm: int):
    """Of visit i: whether its tile lies inside its group;
    ``mine(shape, start)``, the mask (rows along axis 0 of ``shape``) of
    the group's rows among those from row ``start`` of the tile on; and
    ``touches(start, rows)``, whether any of those rows is the group's."""
    g = gids[i]
    lo, hi = offsets[g], offsets[g + 1]
    row0 = tiles[i] * tm
    whole = jnp.logical_and(lo <= row0, row0 + tm <= hi)

    def mine(shape, start=0):
        row = row0 + start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        return jnp.logical_and(row >= lo, row < hi)

    def touches(start, rows):
        return jnp.logical_and(row0 + start < hi, row0 + start + rows > lo)

    return whole, mine, touches


# ---------------------------------------------------------------------------
# ff_gmm and ff_gmm_t: grid (column tiles, visits, depth steps)


def _gmm_kernel(offsets, gids, tiles, counts, a_ref, w_ref, o_ref, *acc,
                tm, sub, depth_steps, transposed):
    # once a trace of the body
    obs.count("kernels.traced.ff_gmm_t" if transposed
              else "kernels.traced.ff_gmm")
    i, ci = pl.program_id(1), pl.program_id(2)
    _, mine, touches = _rows_of(offsets, gids, tiles, i, tm)
    fresh = jnp.logical_or(i == 0, tiles[i] != tiles[jnp.maximum(i - 1, 0)])

    # the first visit of a tile clears it, be it a group's visit or, past
    # the last group, nobody's
    @pl.when(jnp.logical_and(jnp.logical_and(fresh, i < counts[1]), ci == 0))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def put(rows, res):
        o_ref[rows, :] = jnp.where(mine(res.shape, rows.start),
                                   res.astype(o_ref.dtype), o_ref[rows, :])

    # a visit computes the pieces of ``sub`` rows that hold rows of its
    # group and keeps, of those, its group's rows: a tile a boundary cuts
    # costs each of its groups its own pieces only.  (One loop for every
    # tile: a whole tile in one product is no faster, 0.575 against 0.60 ms
    # at four times the grid steps, and would be a second copy of the
    # unrolled product in every one of a step's 45 kernels)
    def piece(p, carry):
        rows = pl.ds(pl.multiple_of(p * sub, sub), sub)

        @pl.when(jnp.logical_and(i < counts[0], touches(rows.start, sub)))
        def _():
            part = jax.lax.dot_general(
                a_ref[rows, :], w_ref[...],
                (((1,), (1 if transposed else 0,)), ((), ())),
                preferred_element_type=_F32)
            if depth_steps == 1:
                put(rows, part)
                return
            acc_ref, = acc

            @pl.when(ci == 0)
            def _():
                acc_ref[rows, :] = part

            @pl.when(ci > 0)
            def _():
                acc_ref[rows, :] += part

            @pl.when(ci == depth_steps - 1)
            def _():
                put(rows, acc_ref[rows, :])

        return carry

    jax.lax.fori_loop(0, tm // sub, piece, 0)


def _gmm_call(a, w, visits, *, tiles, transposed, out_dtype, interpret):
    tm, tk, tn = tiles
    m, k = a.shape
    n = w.shape[1] if transposed else w.shape[2]
    if m % tm or k % tk:
        raise ValueError(f"ff_gmm: tiles {tiles} do not divide {a.shape}")
    depth_steps = k // tk
    n_visits = visits[1].shape[0]

    # a visit that computes nothing fetches nothing: it names the blocks
    # the last computing visit held
    def a_map(ni, i, ci, offsets, gids, tiles_, counts):
        at = jnp.minimum(i, jnp.maximum(counts[0] - 1, 0))
        return tiles_[at], jnp.where(i < counts[0], ci, depth_steps - 1)

    def w_map(ni, i, ci, offsets, gids, tiles_, counts):
        ci = jnp.where(i < counts[0], ci, depth_steps - 1)
        return (gids[i], ni, ci) if transposed else (gids[i], ci, ni)

    def o_map(ni, i, ci, offsets, gids, tiles_, counts):
        return tiles_[i], ni

    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, sub=min(tm, _SUB_ROWS),
                          depth_steps=depth_steps, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(_cdiv(n, tn), n_visits, depth_steps),
            in_specs=[pl.BlockSpec((tm, tk), a_map),
                      pl.BlockSpec((None, tn, tk) if transposed
                                   else (None, tk, tn), w_map)],
            out_specs=pl.BlockSpec((tm, tn), o_map),
            scratch_shapes=([pltpu.VMEM((tm, tn), _F32)]
                            if depth_steps > 1 else [])),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
        name="ff_gmm_t" if transposed else "ff_gmm",
        **_params(interpret),
    )(*visits, a, w)


# ---------------------------------------------------------------------------
# ff_gmm_dw: grid (n tiles, k tiles, visits)


def _dw_kernel(offsets, gids, tiles, counts, a_ref, b_ref, o_ref, acc_ref,
               *, tm, n_visits):
    obs.count("kernels.traced.ff_gmm_dw")
    i = pl.program_id(2)
    g = gids[i]
    live = i < counts[0]
    first = jnp.logical_or(i == 0, g != gids[jnp.maximum(i - 1, 0)])
    last = jnp.logical_or(i == counts[0] - 1,
                          g != gids[jnp.minimum(i + 1, n_visits - 1)])
    whole, mine, _ = _rows_of(offsets, gids, tiles, i, tm)
    some = offsets[g + 1] > offsets[g]

    def tn_dot(a, b):
        return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                                   preferred_element_type=_F32)

    @pl.when(jnp.logical_and(live, first))
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    @pl.when(jnp.logical_and(live, whole))
    def _():
        acc_ref[...] += tn_dot(a_ref[...], b_ref[...])

    @pl.when(jnp.logical_and(live, jnp.logical_and(jnp.logical_not(whole),
                                                   some)))
    def _():
        def masked(ref):
            return jnp.where(mine(ref.shape), ref[...],
                             jnp.zeros((), ref.dtype))

        acc_ref[...] += tn_dot(masked(a_ref), masked(b_ref))

    @pl.when(jnp.logical_and(live, last))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _dw_call(a, b, visits, *, tiles, out_dtype, interpret):
    tm, tk, tn = tiles
    m, k = a.shape
    n = b.shape[1]
    if m % tm:
        raise ValueError(f"ff_gmm_dw: a row tile of {tm}, {m} rows")
    g = visits[0].shape[0] - 1
    n_visits = visits[1].shape[0]

    def a_map(ni, ki, i, offsets, gids, tiles_, counts):
        return tiles_[i], ki

    def b_map(ni, ki, i, offsets, gids, tiles_, counts):
        return tiles_[i], ni

    def o_map(ni, ki, i, offsets, gids, tiles_, counts):
        return gids[i], ki, ni

    return pl.pallas_call(
        functools.partial(_dw_kernel, tm=tm, n_visits=n_visits),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(_cdiv(n, tn), _cdiv(k, tk), n_visits),
            in_specs=[pl.BlockSpec((tm, tk), a_map),
                      pl.BlockSpec((tm, tn), b_map)],
            out_specs=pl.BlockSpec((None, tk, tn), o_map),
            scratch_shapes=[pltpu.VMEM((tk, tn), _F32)]),
        out_shape=jax.ShapeDtypeStruct((g, k, n), out_dtype),
        interpret=interpret,
        name="ff_gmm_dw",
        **_params(interpret),
    )(*visits, a, b)


# ---------------------------------------------------------------------------
# the three forms by name, and the gated feed-forward that uses all three


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _form(form: str, a, other, group_sizes, out_dtype, tiles, interpret):
    """One product alone (tests, tools/chip_kernels.py): the tiles the
    shapes pick unless given, its own walk."""
    m, k = a.shape
    n = other.shape[2 if form == "gmm" else 1]
    out_dtype = jnp.dtype(out_dtype or a.dtype)
    if tiles is None:
        tiles = _pick_tiles(form, m, k, n, a.dtype.itemsize,
                            out_dtype.itemsize)
    if tiles is None:
        raise ValueError(f"ff_{form}: no tiles for {a.shape} x {other.shape}")
    interpret = _should_interpret() if interpret is None else interpret
    visits = _visits(group_sizes, m, tiles[0], form == "dw")
    if form == "dw":
        return _dw_call(a, other, visits, tiles=tiles, out_dtype=out_dtype,
                        interpret=interpret)
    return _gmm_call(a, other, visits, tiles=tiles,
                     transposed=form == "gmm_t", out_dtype=out_dtype,
                     interpret=interpret)


def ff_gmm(a, w, group_sizes, out_dtype=None, tiles=None, interpret=None):
    """out[r] = a[r] @ w[group of r], 0 for a row of no group.
    a (m, c), w (G, c, n) -> (m, n) in ``out_dtype`` (a's by default)."""
    return _form("gmm", a, w, group_sizes, out_dtype, tiles, interpret)


def ff_gmm_t(a, w, group_sizes, out_dtype=None, tiles=None, interpret=None):
    """out[r] = a[r] @ w[group of r]^T.  a (m, c), w (G, n, c) -> (m, n)."""
    return _form("gmm_t", a, w, group_sizes, out_dtype, tiles, interpret)


def ff_gmm_dw(a, b, group_sizes, out_dtype=None, tiles=None, interpret=None):
    """out[g] = a[rows of g]^T @ b[rows of g].  a (m, k), b (m, n) ->
    (G, k, n), float32 sums rounded once to ``out_dtype`` (a's by
    default)."""
    return _form("dw", a, b, group_sizes, out_dtype, tiles, interpret)


#: the gate's activation by name: SiLU (SwiGLU, every model's but one)
#: or ReLU (ReGLU, whose backward passes the gradient where the gate's
#: product is above 0)
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


@functools.lru_cache(maxsize=None)
def _make_gated_ffn(m: int, d: int, f: int, groups: int, dtype: str,
                    interpret: bool, act: str = "silu"):
    """(the gated feed-forward of each group's expert on its rows, its
    gate through ``ACTIVATIONS[act]``, as one custom-VJP function of
    (rows, group_sizes, w_gate, w_up, w_down), all operands of one type;
    the tiles of its first product), or None where the shapes are not the
    kernels'.  Built once a configuration: the forward pass and the
    backward pass are each traced once, here."""
    gating = ACTIVATIONS[act]
    size = jnp.dtype(dtype).itemsize
    picked = {
        "up": _pick_tiles("gmm", m, d, f, size, 4),
        "down": _pick_tiles("gmm", m, f, d, size, size),
        "d_h": _pick_tiles("gmm_t", m, d, f, size, size),
        "d_rows": _pick_tiles("gmm_t", m, f, d, size, size),
        "dw_up": _pick_tiles("dw", m, d, f, size, size),
        "dw_down": _pick_tiles("dw", m, f, d, size, size)}
    if None in picked.values():
        return None

    def gmm(which, transposed, out_dtype):
        return functools.partial(
            _gmm_call, tiles=picked[which], transposed=transposed,
            out_dtype=jnp.dtype(out_dtype), interpret=interpret)

    def dw(which):
        return functools.partial(
            _dw_call, tiles=picked[which], out_dtype=jnp.dtype(dtype),
            interpret=interpret)

    # (a jit, so that the second of two calls in one pass reuses the
    # first's trace)
    up, d_rows = jax.jit(gmm("up", False, _F32), inline=True), \
        jax.jit(gmm("d_rows", True, dtype), inline=True)
    dw_up = jax.jit(dw("dw_up"), inline=True)
    down, d_h, dw_down = gmm("down", False, dtype), gmm("d_h", True, dtype), \
        dw("dw_down")
    # one walk for the row products and one for the weight gradients
    tm, tm_dw = picked["up"][0], picked["dw_up"][0]

    def forward(rows, group_sizes, w_gate, w_up, w_down):
        walk = _visits(group_sizes, m, tm, False)
        gate, up_ = up(rows, w_gate, walk), up(rows, w_up, walk)
        h = (gating(gate) * up_).astype(rows.dtype)
        return (down(h, w_down, walk), *walk, gate, up_, h)

    def backward(rows, group_sizes, w_gate, w_up, w_down, *rest):
        *walk, gate, up_, h, d_y = rest
        by_group = _visits(group_sizes, m, tm_dw, True)
        _, pull = jax.vjp(lambda g, u: gating(g) * u, gate, up_)
        d_gate, d_up = (v.astype(rows.dtype) for v in pull(
            d_h(d_y, w_down, walk).astype(_F32)))
        d_x = d_rows(d_gate, w_gate, walk) + d_rows(d_up, w_up, walk)
        return (d_x, dw_up(rows, d_gate, by_group),
                dw_up(rows, d_up, by_group), dw_down(h, d_y, by_group))

    def aval(*shape, of=dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(of))

    operands = (aval(m, d), aval(groups, of="int32"), aval(groups, d, f),
                aval(groups, d, f), aval(groups, f, d))
    forward, (_, *kept) = traced_once(forward, *operands)
    backward, _ = traced_once(backward, *operands, *kept, aval(m, d))

    @jax.custom_vjp
    def ffn(*operands):
        return forward(*operands)[0]

    def ffn_fwd(*operands):
        y, *kept = forward(*operands)
        return y, (*operands, *kept)

    def ffn_bwd(res, d_y):
        d_x, *d_ws = backward(*res, d_y)
        return (d_x, None, *d_ws)

    ffn.defvjp(ffn_fwd, ffn_bwd)
    return ffn, picked["up"]


def gated_ffn(rows, group_sizes, w_gate, w_up, w_down, interpret=None,
              act: str = "silu"):
    """``(y, tiles)`` with y the gated feed-forward
    ``(act(rows @ w_gate[g]) * (rows @ w_up[g])) @ w_down[g]`` of each
    group's rows through the kernels (``act`` one of ``ACTIVATIONS``),
    or None where the shapes or types are not theirs (the caller keeps
    ``jax.lax.ragged_dot``).  rows (m, d); w_gate, w_up (G, d, f) and
    w_down (G, f, d) in rows' type."""
    (m, d), f = rows.shape, w_gate.shape[2]
    if not all(w.dtype == rows.dtype for w in (w_gate, w_up, w_down)):
        return None
    interpret = _should_interpret() if interpret is None else interpret
    made = _make_gated_ffn(m, d, f, w_gate.shape[0], rows.dtype.name,
                           interpret, act)
    if made is None:
        return None
    ffn, tiles = made
    return ffn(rows, group_sizes, w_gate, w_up, w_down), tiles
