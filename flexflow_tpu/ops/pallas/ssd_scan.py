"""The chunked state-space scan (``ops/ssm.py: ssd_chunked``) as two Pallas
TPU kernels, ``ff_ssd_fwd`` and ``ff_ssd_bwd``: everything a chunk's
``(heads, chunk, chunk)`` matrices feed is computed where they are made,
in VMEM, and none of them reaches HBM in any pass.

A grid step is one (sequence, chunk, group of ``GROUP`` heads); the chunks
of a sequence are walked in order (backward: in reverse) and the state
that enters a chunk, ``(heads * head_dim, d_state)`` float32, is carried
from step to step in a VMEM scratch, which is the recurrence over chunks
(``ssd_chunked`` writes every chunk's own state to HBM and runs the
recurrence as a product over them).  In a step, for the group's heads:

    cb     = C B^T                       once a chunk, shared by the heads
    decay  = exp(cs_t - cs_s), s <= t    (chunk, chunk) float32, a head
    m      = (cb * decay) rounded to the compute type
    y      = m (delta x) + exp(cs_t) C H_in + D x
    H_out  = exp(cs_end) H_in + (delta exp(cs_end - cs) x)^T B

with the roundings where ``ssd_chunked`` has them: cumulative sums,
decays and the carried state float32, the products' operands in the
compute type with float32 accumulation.  A head's decay matrix is made 128
rows at a time and each block only as wide as its rows see, so nothing
right of the diagonal's block is made or multiplied.  The forward writes
``y`` and the state that entered every chunk (float32, what the backward
needs of the recurrence); the backward makes ``cb``, ``decay``, ``m`` and
``m (delta x)`` again from the cumulative sums, as the flash backward
makes its scores again, carries the entering state's gradient from chunk
to earlier chunk in VMEM and returns the gradients of ``x``, ``B``, ``C``
and ``D`` and, a head and step, of the cumulative sum and of ``delta``.

What XLA keeps (it costs nothing a trace shows): ``cs``, the cumulative
sum of ``delta * A`` over a chunk, and its gradient, from which
``delta``'s and ``A_log``'s follow by autodiff; packing them.  The
kernels read ``x``, ``B`` and ``C`` where the mixer's projection left
them, as column blocks of ``xBC`` ``(B, S, heads * head_dim + 2 *
d_state)``, and write the gradient into an array of that shape, so no
slice or concatenation of the wide operand is made around them.

Three things the chip taught (PERF.md section 6, PR 35).  Everything that
is one number a head and step (``delta``, ``exp(cs)``, the decay to the
chunk's end) works on the whole group's ``(chunk, GROUP * head_dim)``
tile at once, the number spread over its head's columns: a 64-wide head
alone fills half of every vreg.  The per-head vectors reach a step as
rows, ``(2 * GROUP, chunk)`` (an array ``(.., chunk, GROUP)`` would be
padded to 128 lanes in HBM, sixteen times its size), and are turned to
run down the sublanes by the MXU (:func:`_down_columns`); sums over a
head's columns are taken by the MXU too (:func:`_head_sums`): what
crosses lanes through the XLU, a ``(chunk, 1)`` slice broadcast again or
a reduction along lanes, costs three to ten cycles a vreg, and the first
kernels spent more than half their time there.  And the decay matrix's
part of ``d cs`` needs no ``(chunk, chunk)`` sum: along a row of ``d m *
m`` it is ``dy . y1``, down a column ``(delta x) . d(delta x)``.

Which shapes take the kernels is :func:`fits`; the Granite cell's (chunk
256, 64 heads of 64, state 128, bfloat16) is the one they were built and
timed for.
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
#: heads a grid step: the sublanes of a float32 tile, which the heads'
#: rows of ``cs`` and ``delta`` fill
GROUP = 8
_F32 = jnp.float32
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
#: the widest ``x`` (heads * head_dim) the rule takes: every head's carried
#: state, (width, 128) float32, stays in VMEM over a sequence, 2 MB here
MAX_WIDTH = 4096


def fits(chunk: int, heads: int, head_dim: int, state: int, dtype) -> bool:
    """Whether the kernels take a scan of these shapes.  They hold what
    they won and little beside it: the Granite cell's shape (chunk 256, 64
    heads of 64, state 128, bfloat16) is the one timed on the chip; the
    rest of the rule is what the CPU tests run in interpret mode, compiled
    by Mosaic at the rule's corners and not timed
    (``tests/test_kernels_compile_for_v5e.py``).  A chunk of 128 or 256
    (whole lanes: it is the width of the score matrix); heads in groups of
    ``GROUP``, 16, 32 or 64 wide (a group's ``x`` is whole lanes), up to
    ``MAX_WIDTH`` columns in all; a state of 128, one lane tile (``B`` and
    ``C`` are the two column blocks of ``xBC`` behind the heads; at 256
    Mosaic's own checks fail while it compiles); bfloat16 or float32
    operands."""
    return (chunk in (LANES, 2 * LANES) and heads % GROUP == 0
            and head_dim in (16, 32, 64) and heads * head_dim <= MAX_WIDTH
            and state == LANES
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def _nt(a, b):
    """a (m, k) x b (n, k)^T -> (m, n) float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=_F32)


def _tn(a, b):
    """a (k, m)^T x b (k, n) -> (m, n) float32."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=_F32)


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=_F32)


def _pieces(v):
    """A float32 array as three bfloat16 arrays that add back to it
    exactly: 8 bits of its 24 each."""
    high = v.astype(jnp.bfloat16)
    rest = v - high.astype(_F32)
    mid = rest.astype(jnp.bfloat16)
    return [high, mid, (rest - mid.astype(_F32)).astype(jnp.bfloat16)]


def _picks(rows, columns, per):
    """(rows, columns) of 0 and 1, bfloat16: row r picks the columns of
    head ``r % GROUP``, ``per`` columns a head."""
    head = jax.lax.broadcasted_iota(jnp.int32, (rows, columns), 0) % GROUP
    to = jax.lax.broadcasted_iota(jnp.int32, (rows, columns), 1) // per
    return (head == to).astype(jnp.bfloat16)


def _down_columns(rows, p):
    """The group's per-step vectors, which reach a step as rows (GROUP,
    chunk), in the forms that meet a matrix with the steps down its
    sublanes: ``cs`` of head j in every lane of ``[:, j * 128:(j + 1) *
    128]`` (for the head's decay matrix), and ``cs`` and ``delta`` of head
    j in the lanes of the head's own columns of x, (chunk, GROUP * p).

    Through the MXU, exactly: a float32 is the sum of three bfloat16s, and
    a product with a matrix of 0 and 1 adds the three back in float32.
    (A (chunk, 1) slice of a transposed tile is broadcast along the lanes
    again at every use, about three cycles a vreg: it cost the forward
    kernel 1.27 of 2.35 ms; PERF.md section 6, PR 35.)"""
    # (stacked as float32: a bfloat16 tile holds 16 rows, a piece 8)
    cs, dt = (jnp.concatenate(
        [w.astype(_F32) for w in _pieces(v)] + [jnp.zeros_like(v)], 0
    ).astype(jnp.bfloat16) for v in (rows[:GROUP], rows[GROUP:]))
    own = _picks(4 * GROUP, GROUP * p, p)
    return (_tn(cs, _picks(4 * GROUP, GROUP * LANES, LANES)), _tn(cs, own),
            _tn(dt, own))


def _last_row(v):
    """The last row of v as (1, lanes), through a reduction (Mosaic folds
    a slice that is broadcast again into a broadcast of a (1, 1) along
    both axes, which it does not lower); v <= 0."""
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, v.shape[1]), 0)
    return jnp.max(jnp.where(sub == 7, v[v.shape[0] - 8:, :], -jnp.inf),
                   axis=0, keepdims=True)


def _state_decay(rows, n):
    """``exp(cs_end)`` of a group's heads, (GROUP, n): head j's along the
    lanes of row j, to scale the head's rows of a state."""
    return jnp.exp(jnp.broadcast_to(rows[:GROUP, rows.shape[1] - 1:],
                                    (GROUP, n)))


@functools.partial(jax.jit, static_argnames="dtype")
def _m_block(scores, cs, cs_r, seen, dtype):
    """``(m, decay)`` of a block of a head's rows: ``decay[t, s] = exp(cs_t
    - cs_s)`` where ``seen`` and 0 elsewhere, float32, and ``m = scores *
    decay`` rounded to ``dtype``.  scores, seen (128, k); cs (128, 128)
    with every lane alike; cs_r (1, k).  (A ``jit`` so that a kernel's
    eight heads share one trace of it: tracing the kernels is set-up
    time, 2.7 s of the cell's on the chip machine's host before.)"""
    along = jnp.concatenate([cs] * (seen.shape[1] // LANES), 1) - cs_r
    decay = jnp.exp(jnp.where(seen, along, -jnp.inf))
    return (scores * decay).astype(dtype), decay


def _row_blocks(l):
    """A chunk's decay matrix 128 rows at a time, each block as wide as
    its rows see (nothing right of the diagonal's block is made: a
    quarter of a 256-step chunk's matrix): ``[(rows, k, seen)]``, seen
    (128, k) the mask of the steps a row's step sees."""
    out = []
    for i in range(l // LANES):
        k = (i + 1) * LANES
        seen = (i * LANES
                + jax.lax.broadcasted_iota(jnp.int32, (LANES, k), 0)
                >= jax.lax.broadcasted_iota(jnp.int32, (LANES, k), 1))
        out.append((slice(i * LANES, k), k, seen))
    return out


def _fwd_kernel(rows_ref, skip_ref, x_ref, b_ref, c_ref, y_ref, hin_ref,
                state, cb, dtx, y1, *, p):
    obs.count("kernels.traced.ff_ssd_fwd")    # once a trace of the body
    ci, g = pl.program_id(1), pl.program_id(2)
    cdt = x_ref.dtype

    @pl.when(ci == 0)
    def _():
        state[g] = jnp.zeros(state.shape[1:], _F32)

    @pl.when(g == 0)
    def _():
        cb[...] = _nt(c_ref[...], b_ref[...])

    rows = rows_ref[...]
    cs_full, cs, dt = _down_columns(rows, p)
    x = x_ref[...].astype(_F32)                    # (L, group * P)
    dtx[...] = (x * dt).astype(cdt)
    # inside the chunk, a head at a time: (L * C B^T) (delta x)
    blocks = _row_blocks(x.shape[0])
    for j in range(GROUP):
        at = slice(j * p, (j + 1) * p)
        for r, k, seen in blocks:
            m, _ = _m_block(cb[r, :k], cs_full[r, j * LANES:(j + 1) * LANES],
                            rows[j:j + 1, :k], seen, dtype=cdt)
            y1[r, at] = _nn(m, dtx[:k, at])
    # what the entering state adds, exp(cs) C H_in^T, and the skip
    hin = state[g]
    hin_ref[...] = hin
    y = y1[...] + jnp.exp(cs) * _nt(c_ref[...], hin.astype(cdt))
    y_ref[...] = (y + skip_ref[...] * x).astype(y_ref.dtype)
    # the chunk's own state, and the state the next chunk enters with
    to_end = jnp.exp(_last_row(cs) - cs)
    own = _tn((x * (dt * to_end)).astype(cdt), b_ref[...])
    ends = _state_decay(rows, hin.shape[1])
    for j in range(GROUP):
        at = slice(j * p, (j + 1) * p)
        state[g, at, :] = ends[j:j + 1] * hin[at] + own[at]


def _head_sums(v, p):
    """v (chunk, GROUP * p) float32 summed over each head's columns ->
    (chunk, 128) with head j's sums in the lanes ``j, j + GROUP, ..``:
    through the MXU, every addend whole as three bfloat16s, so the sums
    are float32's as ``ssd_chunked``'s are (a reduction along lanes costs
    the XLU some ten cycles a vreg, and a step has a thousand of them;
    two pieces, 16 bits of an addend, read 0.2 ms a layer less on the
    chip and moved no gradient beyond the fifth digit: PERF.md section
    6, PR 35)."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, (v.shape[1], LANES), 1)
    heads = jax.lax.broadcasted_iota(jnp.int32, (v.shape[1], LANES), 0) // p
    pick = (heads == lanes % GROUP).astype(jnp.bfloat16)
    return sum(_nn(w, pick) for w in _pieces(v))


def _bwd_kernel(rows_ref, skip_ref, x_ref, b_ref, c_ref, dy_ref, hin_ref,
                dx_ref, dbc_ref, drows_ref, dskip_ref,
                dstate, cb, dcb, db, dc, m, dtx, y1, d_dtx, *, p, n):
    obs.count("kernels.traced.ff_ssd_bwd")
    ci, g = pl.program_id(1), pl.program_id(2)
    groups = pl.num_programs(2)
    l, cdt = x_ref.shape[0], x_ref.dtype

    @pl.when(ci == 0)
    def _():
        dstate[g] = jnp.zeros(dstate.shape[1:], _F32)

    @pl.when(g == 0)
    def _():
        cb[...] = _nt(c_ref[...], b_ref[...])
        dcb[...] = jnp.zeros(dcb.shape, _F32)
        db[...] = jnp.zeros(db.shape, _F32)
        dc[...] = jnp.zeros(dc.shape, _F32)

    rows = rows_ref[...]
    cs_full, cs, dt = _down_columns(rows, p)
    x, dy = x_ref[...].astype(_F32), dy_ref[...].astype(_F32)
    dtx[...] = (x * dt).astype(cdt)
    # y1 = m (delta x) a head at a time, a block of rows at a time: m and
    # y1 made again, d m into d(C B^T), m^T dy
    blocks = _row_blocks(l)
    for j in range(GROUP):
        at = slice(j * p, (j + 1) * p)
        for r, k, seen in blocks:
            m[r, :k], decay = _m_block(
                cb[r, :k], cs_full[r, j * LANES:(j + 1) * LANES],
                rows[j:j + 1, :k], seen, dtype=cdt)
            y1[r, at] = _nn(m[r, :k], dtx[:k, at])
            dcb[r, :k] += _nt(dy_ref[r, at], dtx[:k, at]) * decay
        for c in range(0, l, LANES):
            d_dtx[c:c + LANES, at] = _tn(m[c:, c:c + LANES], dy_ref[c:, at])
    hin, dhout = hin_ref[...], dstate[g]
    hin_c, dhout_c = hin.astype(cdt), dhout.astype(cdt)
    grow = jnp.exp(cs)
    y2 = grow * _nt(c_ref[...], hin_c)             # exp(cs) C H_in^T
    dxe = _nt(b_ref[...], dhout_c)                 # d(delta to_end x)
    to_end = jnp.exp(_last_row(cs) - cs)
    to_state = dt * to_end
    dz = (dy * grow).astype(cdt)
    xe = (x * to_state).astype(cdt)
    dx_ref[...] = (d_dtx[...] * dt + dxe * to_state + skip_ref[...] * dy
                   ).astype(dx_ref.dtype)
    dskip_ref[...] = jnp.sum(dy * x, axis=0, keepdims=True)
    # d cs and d delta, a head's sums over its columns.  The decay
    # matrix's part needs no (chunk, chunk) sum: along a row of d m * m it
    # is dy . y1, down a column (delta x) . d(delta x), both with m and
    # delta x as the products took them, rounded, so that the two cancel
    # over a chunk as the sums of one matrix do (with delta x unrounded
    # in one of them A_log's gradient, what is left after they cancel,
    # read 44% off on the chip)
    held = dxe * x * to_state                      # d to_end * to_end
    d_dt = _head_sums((d_dtx[...] + dxe * to_end) * x, p)
    d_cs = _head_sums(dy * (y1[...] + y2) - d_dtx[...] * dtx[...].astype(_F32)
                      - held, p)
    # exp(cs_end): in every step's to_end and in the state handed on
    moved = jnp.sum(held, axis=0, keepdims=True)
    ends = _state_decay(rows, n)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    d_end = jnp.zeros((1, LANES), _F32)
    for j in range(GROUP):
        at = slice(j * p, (j + 1) * p)
        d_end = jnp.where(
            lane == j,
            jnp.sum(moved[:, at], axis=1, keepdims=True) + ends[j:j + 1, :1]
            * jnp.sum(dhout[at] * hin[at], keepdims=True), d_end)
    last = jax.lax.broadcasted_iota(jnp.int32, (l, LANES), 0) == l - 1
    d_cs = d_cs + jnp.where(last, d_end, 0.0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (l, LANES), 1)
    drows_ref[...] = jnp.where(lanes < GROUP, d_cs, d_dt).T[:2 * GROUP]
    # the group's part of dC, dB and of the entering state's gradient
    dc[...] += _nn(dz, hin_c)
    db[...] += _nn(xe, dhout_c)
    dhin = _tn(dz, c_ref[...])
    for j in range(GROUP):
        at = slice(j * p, (j + 1) * p)
        dstate[g, at, :] = ends[j:j + 1] * dhout[at] + dhin[at]

    @pl.when(g == groups - 1)
    def _():
        d_scores = dcb[...].astype(cdt)
        dbc_ref[:, :n] = (db[...] + _tn(d_scores, c_ref[...])
                          ).astype(dbc_ref.dtype)
        dbc_ref[:, n:] = (dc[...] + _nn(d_scores, b_ref[...])
                          ).astype(dbc_ref.dtype)


def _params(interpret):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)}


def _specs(l, p, n, width, chunks, backward):
    """The block specs of (rows, skip, x, B, C) and the maps of a
    (sequence, chunk, group) step to a block of chunks and of head groups;
    the backward walks a sequence's chunks from the last to the first."""
    gp = GROUP * p

    def chunk(c):
        return chunks - 1 - c if backward else c

    def per_group(*shape):
        return pl.BlockSpec((None, None, *shape),
                            lambda b, c, g: (b, chunk(c), g, 0))

    def per_chunk(columns, block):
        return pl.BlockSpec((None, l, columns),
                            lambda b, c, g: (b, chunk(c), block))

    xs = pl.BlockSpec((None, l, gp), lambda b, c, g: (b, chunk(c), g))
    def of_group(rows, lanes):
        return pl.BlockSpec((None, None, None, rows, lanes),
                            lambda b, c, g: (b, chunk(c), g, 0, 0))

    rows = of_group(2 * GROUP, l)
    skip = pl.BlockSpec((1, gp), lambda b, c, g: (0, g))
    return (rows, skip, xs, per_chunk(n, width // n),
            per_chunk(n, width // n + 1)), per_group, per_chunk, of_group


def _fwd_call(rows, skip, xbc, *, p, n, interpret):
    bsz, chunks, groups, _, l = rows.shape
    width, gp = groups * GROUP * p, GROUP * p
    ins, per_group, _, _ = _specs(l, p, n, width, chunks, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p),
        grid=(bsz, chunks, groups),
        in_specs=list(ins),
        out_specs=[ins[2], per_group(gp, n)],
        out_shape=[jax.ShapeDtypeStruct((bsz, chunks * l, width), xbc.dtype),
                   jax.ShapeDtypeStruct((bsz, chunks, width, n), _F32)],
        scratch_shapes=[pltpu.VMEM((groups, gp, n), _F32),
                        pltpu.VMEM((l, l), _F32),
                        pltpu.VMEM((l, gp), xbc.dtype),
                        pltpu.VMEM((l, gp), _F32)],
        interpret=interpret,
        name="ff_ssd_fwd",
        **_params(interpret),
    )(rows, skip, xbc, xbc, xbc)


def _bwd_call(rows, skip, xbc, dy, hin, *, p, n, interpret):
    bsz, chunks, groups, _, l = rows.shape
    width, gp = groups * GROUP * p, GROUP * p
    ins, per_group, per_chunk, of_group = _specs(l, p, n, width, chunks,
                                                 True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, p=p, n=n),
        grid=(bsz, chunks, groups),
        in_specs=[*ins, ins[2], per_group(gp, n)],
        # dx fills the head columns of an array as wide as xBC; dB and dC
        # leave side by side and the caller writes them behind dx; D's
        # gradient a (sequence, chunk), summed by the caller
        out_specs=[ins[2], per_chunk(2 * n, 0), ins[0], of_group(1, gp)],
        out_shape=[jax.ShapeDtypeStruct(xbc.shape, xbc.dtype),
                   jax.ShapeDtypeStruct((bsz, chunks * l, 2 * n), xbc.dtype),
                   jax.ShapeDtypeStruct(rows.shape, _F32),
                   jax.ShapeDtypeStruct((bsz, chunks, groups, 1, gp), _F32)],
        scratch_shapes=[pltpu.VMEM((groups, gp, n), _F32),
                        pltpu.VMEM((l, l), _F32), pltpu.VMEM((l, l), _F32),
                        pltpu.VMEM((l, n), _F32), pltpu.VMEM((l, n), _F32),
                        pltpu.VMEM((l, l), xbc.dtype),
                        pltpu.VMEM((l, gp), xbc.dtype),
                        pltpu.VMEM((l, gp), _F32), pltpu.VMEM((l, gp), _F32)],
        interpret=interpret,
        name="ff_ssd_bwd",
        **_params(interpret),
    )(rows, skip, xbc, xbc, xbc, dy, hin)


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=None)
def _make_scan(bsz: int, chunks: int, l: int, heads: int, p: int, n: int,
               dtype: str, interpret: bool):
    """The scan of ``chunks`` chunks of ``l`` steps as one custom-VJP
    function of (rows, skip, xBC) -> y.  Built once a configuration: the
    forward and the backward are each traced once, here, and bound
    equation by equation wherever a layer calls them (``traced_once``:
    nine layers, the blocks they are recomputed in and their derivatives
    share one trace of each kernel body)."""
    width, groups = heads * p, heads // GROUP
    rows = jax.ShapeDtypeStruct((bsz, chunks, groups, 2 * GROUP, l), _F32)
    skip = jax.ShapeDtypeStruct((1, width), _F32)
    xbc = jax.ShapeDtypeStruct((bsz, chunks * l, width + 2 * n),
                               jnp.dtype(dtype))
    dy = jax.ShapeDtypeStruct((bsz, chunks * l, width), jnp.dtype(dtype))

    def forward(rows, skip, xbc):
        return _fwd_call(rows, skip, xbc, p=p, n=n, interpret=interpret)

    def backward(rows, skip, xbc, dy, hin):
        dxbc, dbc, drows, dskip = _bwd_call(rows, skip, xbc, dy, hin, p=p,
                                            n=n, interpret=interpret)
        return (drows, jnp.sum(dskip, axis=(0, 1)).reshape(1, width),
                jax.lax.dynamic_update_slice_in_dim(dxbc, dbc, width, axis=2))

    forward, (_, hin) = traced_once(forward, rows, skip, xbc)
    backward, _ = traced_once(backward, rows, skip, xbc, dy, hin)

    @jax.custom_vjp
    def scan(*operands):
        return forward(*operands)[0]

    def scan_fwd(*operands):
        y, hin = forward(*operands)
        return y, (*operands, hin)

    def scan_bwd(res, dy):
        *operands, hin = res
        return tuple(backward(*operands, dy, hin))

    scan.defvjp(scan_fwd, scan_bwd)
    return scan


def ssd_scan(xbc, dt, a, d, *, heads: int, head_dim: int, state: int,
             chunk: int, interpret=None):
    """``ssd_chunked`` on the mixer's own arrays: xbc (B, S, heads *
    head_dim + 2 * state) = [x | B | C] in the compute type, dt (B, S,
    heads) float32 time steps, a (heads,) float32 and negative, d (heads,)
    float32 -> y (B, S, heads * head_dim) as xbc.  The shapes must be the
    kernels' (:func:`fits`); a sequence the chunk does not divide is
    padded with steps of ``delta = 0``."""
    bsz, s, _ = xbc.shape
    l = max(1, min(int(chunk), s))
    if not fits(l, heads, head_dim, state, xbc.dtype):
        raise ValueError(
            f"ff_ssd: a chunk of {l}, {heads} heads of {head_dim}, state "
            f"{state}, {xbc.dtype} are not the kernels' shapes")
    pad = -s % l
    if pad:
        xbc, dt = (jnp.pad(v, ((0, 0), (0, pad), (0, 0))) for v in (xbc, dt))
    chunks, groups = (s + pad) // l, heads // GROUP
    # rows of a (sequence, chunk, group): cs and delta of its heads; D
    # along the columns of x
    dtc = dt.astype(_F32).reshape(bsz, chunks, l, groups, GROUP)
    cs = jnp.cumsum(dtc * a.astype(_F32).reshape(groups, GROUP), axis=2)
    rows = jnp.concatenate([cs, dtc], axis=-1).transpose(0, 1, 3, 4, 2)
    skip = jnp.repeat(d.astype(_F32), head_dim).reshape(1, heads * head_dim)
    interpret = _should_interpret() if interpret is None else interpret
    scan = _make_scan(bsz, chunks, l, heads, head_dim, state,
                      xbc.dtype.name, bool(interpret))
    y = scan(rows, skip, xbc)
    return y[:, :s] if pad else y
