"""Fused vocab-projection + softmax-cross-entropy as Pallas TPU kernels.

The (N, V) logits matrix — the largest tensor in an LM/NMT training step
(16 384 tokens x 100 352 columns = 6.6 GB in f32 on the Granite cell) —
never reaches HBM: each (block_n, block_v) logits tile is computed on the
MXU from the resident activation block and streamed through a running
log-sum-exp, exactly the flash-attention recipe applied to the classifier
head.  The backward pass makes each tile once more from the saved per-row
lse, forms ``g * (softmax - onehot)`` and feeds dx, dw and db from it: the
logits are made twice a step and the head is four vocabulary-sized
products (PR 33; until then dx and dw each had a kernel and a tile of
their own: three times and five).

Replaces the unfused pair RnnLinear -> SoftmaxDP (reference:
nmt/linear.cu + nmt/softmax_data_parallel.cu, which materialize the full
logits region between the two task launches) when the FFModel apply-time
fusion pass fires — see FFModel._lm_head_fusion.

**The backward's two sums.**  ``ff_ce_bwd`` walks token blocks (outer
axis) and, inside each, the vocabulary (inner axis).  dx sums over the
inner axis in a float32 VMEM scratch and leaves once a token block.  dw
and db sum over the OUTER axis, through HBM: the float32 result is both
an input and the output of the call (``input_output_aliases``); at every
step the pipeline fetches the block's earlier sum, the kernel adds the
step's product and the pipeline writes it back (a token block of 1024
gives 614 FLOP a byte of that traffic where the v5e needs 240; the first
token block writes and ignores what it was handed).  dw leaves the
kernel TRANSPOSED, as ``(V, d)`` row-major (``t^T x``: the tile is
transposed, not the wider x block): that is how XLA lays out GPT-2's
``(d, V)`` parameter and what a tied head's table is, so the weight
update reads it with no layout copy (two 154 MB copies a GPT-2 step
otherwise, which the scheduler put at the step's memory peak beside
dw).  The other
arrangement (vocabulary outer, dx through HBM) read 2-5% slower on the
chip at d 2048, where its ``bn x d`` block leaves room for 256 rows
only, and the same at d 768 (PERF.md section 6, PR 33).

**Why a block is never read while its write-back is in flight.**  A block
is written at the end of step ``s`` and fetched again for step ``s +
sweep``, ``sweep`` the vocabulary blocks of a token block; Mosaic's
pipeline holds two buffers a block, so the write of step ``s`` is waited
for before step ``s + 2`` reuses its buffer, and a fetch is issued one
step ahead, at the start of step ``s + sweep - 1``.  With ``sweep >= 4``
a whole step lies between the two; a sweep of 2 does race (measured on
the chip: db wrong by 0.49 of its norm), so the tile rule takes a
vocabulary block that gives a sweep of one block or of at least
``_MIN_SWEEP``, and forced tiles that give 2 or 3 are refused off the
interpreter.  With ONE block a sweep the block index never moves,
nothing is fetched again, and the sum stays in the resident output
block.  The generic interpreter keeps an aliased input and its output
apart (the input stays as it came) but loads output blocks, so under it
the kernel also reads the earlier sum from the output block;
``pltpu.InterpretParams()`` models the one HBM buffer as the chip has it
(tests/test_pallas.py runs both).

**Tiles** are a function of shapes and types (:func:`_pick_tiles`), under
the ``vmem_limit_bytes`` = 64 MiB that flash_attention.py and
grouped_mm.py ask for: a tile of at most 1024 x 1024 float32 logits; the
forward takes the widest vocabulary block (up to 2048: its per-row
statistics cost a step what they cost, however wide), then the tallest
token block; the backward the tallest token block (up to 1024: it sets
the FLOP a byte of the sum through HBM), then the widest vocabulary
block that fits.  At d 768: 512 x 2048 and 1024 x 1024; at d 2048: 512 x
2048 and 1024 x 512; at d 4096: 512 x 2048 and 512 x 256.  On the chip
(tools/chip_kernels.py ce; PERF.md section 6, PR 33) forward and backward
fell from 42.6 to 30.0 ms at GPT-2's shape (16 384 x 768 x 50 257), from
209.7 to 148.7 at Granite's (2048 x 100 352) and from 45.3 to 30.6 at
Moonlight's (2048 x 20 480); in their cells GPT-2 gained 13.2% items a
second, Granite 5.9% and Moonlight 2.1%.

Compiled via Mosaic on TPU; interpreter mode elsewhere (CPU test suite).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu import obs

LANES = 128
_NEG_INF = float("-inf")
# what the kernels ask of a v5e's 128 MiB of VMEM, and what the tile rule
# lets a kernel's blocks, scratch and float32 values add up to
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# float32 logits of one tile: 4 MiB (1024 x 2048 read slower at d 2048)
_TILE_ELEMENTS = 1024 * 1024
# an axis is padded by no more than this share of itself
_PAD_SHARE = 0.10
# blocks of the vocabulary between a block's write and its next read
_MIN_SWEEP = 4


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _params(interpret, semantics):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)}


# ---------------------------------------------------------------------------
# forward: per-token nll = lse(x@w + b) - (x@w + b)[label]


def _fwd_kernel(x_ref, w_ref, b_ref, lab_ref, nll_ref, lse_ref,
                m_scr, l_scr, corr_scr, *, vocab, block_v):
    obs.count("kernels.traced.ff_ce_fwd")     # once a trace of the body
    vi = pl.program_id(1)
    nv = pl.num_programs(1)
    v_off = vi * block_v

    @pl.when(vi == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, m_scr.dtype)
        l_scr[:] = jnp.zeros(l_scr.shape, l_scr.dtype)
        corr_scr[:] = jnp.zeros(corr_scr.shape, corr_scr.dtype)

    logits = jax.lax.dot_general(x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    logits = logits + b_ref[:].astype(jnp.float32)
    vpos = v_off + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    valid = vpos < vocab
    s = jnp.where(valid, logits, _NEG_INF)
    m_prev = m_scr[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    # '& valid' so a remote shard's label landing in [vocab, v_pad) can
    # never match a padded column (robust even if pads were nonzero)
    corr_mask = jnp.logical_and(vpos == lab_ref[:], valid)
    corr_scr[:, 0:1] += jnp.sum(jnp.where(corr_mask, logits, 0.0),
                                axis=-1, keepdims=True)
    scale = jnp.exp(m_prev - m_new)
    l_new = l_scr[:, 0:1] * scale + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(vi == nv - 1)
    def _finish():
        lse = m_scr[:, 0:1] + jnp.log(jnp.maximum(l_scr[:, 0:1], 1e-30))
        lse_ref[:] = lse
        nll_ref[:] = lse - corr_scr[:, 0:1]


def _fwd_call(x, w, b2, lab2, vocab, block_n, block_v, interpret):
    n_p, d_p = x.shape
    v_p = w.shape[1]
    kernel = functools.partial(_fwd_kernel, vocab=vocab, block_v=block_v)
    return pl.pallas_call(
        kernel,
        grid=(n_p // block_n, v_p // block_v),
        in_specs=[
            pl.BlockSpec((block_n, d_p), lambda i, j: (i, 0)),
            pl.BlockSpec((d_p, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_p, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, 128), jnp.float32),
            pltpu.VMEM((block_n, 128), jnp.float32),
            pltpu.VMEM((block_n, 128), jnp.float32),
        ],
        interpret=interpret,
        name="ff_ce_fwd",
        **_params(interpret, ("parallel", "arbitrary")),
    )(x, w, b2, lab2)


# ---------------------------------------------------------------------------
# backward: dlogits = g * (softmax - onehot); dx = dlogits @ wT,
# dwT = dlogitsT @ x, db = sum_rows(dlogits) — each logits tile made once
# more and fed to all three


def _tile_dlogits(x_ref, w_ref, b_ref, lab_ref, lse_ref, gp_ref, goh_ref,
                  v_off, vocab):
    """dlogits tile = g_p * softmax - g_oh * onehot.  For the plain CE op
    g_p == g_oh == g; the partial (vocab-sharded) form folds the lse
    cotangent into g_p (d lse/d logits = softmax)."""
    logits = jax.lax.dot_general(x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    logits = logits + b_ref[:].astype(jnp.float32)
    vpos = v_off + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    valid = vpos < vocab
    p = jnp.where(valid, jnp.exp(logits - lse_ref[:]), 0.0)
    onehot = jnp.where(jnp.logical_and(vpos == lab_ref[:], valid), 1.0, 0.0)
    return gp_ref[:] * p - goh_ref[:] * onehot   # (bn, bv) f32


def _bwd_kernel(x_ref, w_ref, b_ref, lab_ref, lse_ref, gp_ref, goh_ref,
                dw_in, db_in, dx_ref, dw_ref, db_ref, dx_scr, *, vocab,
                block_v, reread):
    obs.count("kernels.traced.ff_ce_bwd")
    ni, vi = pl.program_id(0), pl.program_id(1)
    nv = pl.num_programs(1)
    t = _tile_dlogits(x_ref, w_ref, b_ref, lab_ref, lse_ref, gp_ref,
                      goh_ref, vi * block_v, vocab)
    tc = t.astype(w_ref.dtype)

    # dx: over the vocabulary (inner axis), in scratch
    @pl.when(vi == 0)
    def _init():
        dx_scr[:] = jnp.zeros(dx_scr.shape, dx_scr.dtype)

    dx_scr[:] += jax.lax.dot_general(
        tc, w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(vi == nv - 1)
    def _finish():
        dx_ref[:] = dx_scr[:].astype(dx_ref.dtype)

    # dw (transposed: (bv, d)), db: over the token blocks (outer axis),
    # through HBM
    dw = jax.lax.dot_general(
        tc, x_ref[:], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    db = jnp.sum(t, axis=0, keepdims=True)

    @pl.when(ni == 0)
    def _first():
        dw_ref[:] = dw
        db_ref[:] = db

    @pl.when(ni > 0)
    def _add():
        dw_ref[:] = (dw_in if reread else dw_ref)[:] + dw
        db_ref[:] = (db_in if reread else db_ref)[:] + db


def _bwd_call(x, w, b2, lab2, lse, gp2, goh2, vocab, block_n, block_v,
              interpret):
    n_p, d_p = x.shape
    v_p = w.shape[1]
    sweep = v_p // block_v
    if not interpret and 1 < sweep < _MIN_SWEEP:
        raise ValueError(
            f"ff_ce_bwd: {sweep} vocabulary blocks of {block_v}: a block "
            f"would be read back while its write is in flight")
    f32 = jnp.float32
    rows = pl.BlockSpec((block_n, 1), lambda i, j: (i, 0))
    x_spec = pl.BlockSpec((block_n, d_p), lambda i, j: (i, 0))
    w_spec = pl.BlockSpec((d_p, block_v), lambda i, j: (0, j))
    b_spec = pl.BlockSpec((1, block_v), lambda i, j: (0, j))
    wt_spec = pl.BlockSpec((block_v, d_p), lambda i, j: (j, 0))
    kernel = functools.partial(
        _bwd_kernel, vocab=vocab, block_v=block_v,
        # the earlier sum is in the output block itself where a sweep is
        # one block, and under the generic interpreter (module docstring)
        reread=sweep > 1 and interpret is not True)
    return pl.pallas_call(
        kernel,
        grid=(n_p // block_n, sweep),
        in_specs=[x_spec, w_spec, b_spec, rows, rows, rows, rows,
                  wt_spec, b_spec],
        out_specs=[x_spec, wt_spec, b_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n_p, d_p), x.dtype),
            jax.ShapeDtypeStruct((v_p, d_p), f32),
            jax.ShapeDtypeStruct((1, v_p), f32),
        ],
        scratch_shapes=[pltpu.VMEM((block_n, d_p), f32)],
        input_output_aliases={7: 1, 8: 2},
        interpret=interpret,
        name="ff_ce_bwd",
        **_params(interpret, ("arbitrary", "arbitrary")),
    )(x, w, b2, lab2, lse, gp2, goh2,
      jax.lax.empty((v_p, d_p), f32), jax.lax.empty((1, v_p), f32))


# ---------------------------------------------------------------------------
# tiles


def _fwd_bytes(bn, bv, d_p, itemsize):
    """VMEM the forward asks for: the pipeline's two copies of the x and
    w blocks, the (bn, 128) float32 rows of the running statistics (three
    in scratch, the label and two results twice), and three (bn, bv)
    float32 values alive at once (logits, masked, exp).  Compiles for a
    described v5e took up to 2 MiB more and up to 8 less."""
    blocks = 2 * (bn * d_p + d_p * bv) * itemsize
    rows = (3 + 2 * 3) * bn * LANES * 4
    return blocks + rows + 3 * bn * bv * 4


def _bwd_bytes(bn, bv, d_p, itemsize):
    """VMEM the backward asks for: two copies of the x, w and dx blocks
    and of the dw block coming in and going out, dx's float32 scratch,
    both products' float32 results before they are added, four (bn, 1)
    row inputs at a lane tile each, and two (bn, bv) float32 values.
    Compiles for a described v5e took this or up to 13 MiB less (60 of
    the 64 counted at d 2048, 1024 x 512; 59 of 59 at d 4096, 512 x
    256)."""
    blocks = 2 * (2 * bn * d_p + d_p * bv) * itemsize + 4 * d_p * bv * 4
    sums = (2 * bn * d_p + d_p * bv) * 4
    rows = 2 * 4 * bn * LANES * 4
    return blocks + sums + rows + 2 * bn * bv * 4


def _blocks(size, largest):
    """The blocks an axis of ``size`` may be cut in, largest first:
    ``largest``, its half and so on down to 128 (none more than the axis
    rounded up to whole lanes), those that pad the axis by no more than
    ``_PAD_SHARE`` of itself, and what is left when none does: one block
    over the whole axis, or 128."""
    whole = _round_up(size, LANES)
    out = []
    while largest >= LANES:
        blk = min(largest, whole)
        if blk not in out and (
                blk in (whole, LANES)
                or _round_up(size, blk) - size <= _PAD_SHARE * size):
            out.append(blk)
        largest //= 2
    return out


def _pick_tiles(n, v, d_p, itemsize, backward):
    """(bn, bv) of one kernel, from the shapes and the operands' size
    alone (module docstring): the first tile, in the kernel's order of
    preference, that holds no more than ``_TILE_ELEMENTS`` logits and
    fits the VMEM the kernels ask for; the backward's sweep of the
    vocabulary is one block or at least ``_MIN_SWEEP``."""
    rows, cols = _blocks(n, 1024), _blocks(v, 1024 if backward else 2048)
    order = [(bn, bv) for bn in rows for bv in cols] if backward \
        else [(bn, bv) for bv in cols for bn in rows]
    bytes_of = _bwd_bytes if backward else _fwd_bytes
    for bn, bv in order:
        if (bn * bv <= _TILE_ELEMENTS
                and bytes_of(bn, bv, d_p, itemsize) <= _VMEM_LIMIT_BYTES
                and not (backward
                         and 1 < _round_up(v, bv) // bv < _MIN_SWEEP)):
            return bn, bv
    raise ValueError(f"fused_ce: no tile of width {d_p} fits "
                     f"{_VMEM_LIMIT_BYTES} bytes of VMEM")


# ---------------------------------------------------------------------------
# public op


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=None)
def _make_fused(x_shape, v, xdt, wdt, bdt, block_n, block_v, interpret,
                with_lse=False):
    n, d = x_shape
    # interpret mode has no lanes to fill: the width stays as it is
    d_p = d if interpret else _round_up(d, LANES)
    itemsize = jnp.dtype(xdt).itemsize
    fwd = _pick_tiles(n, v, d_p, itemsize, backward=False)
    bwd = _pick_tiles(n, v, d_p, itemsize, backward=True)
    if block_n is not None or block_v is not None:
        # the tests' way to small tiles
        sub = 8 if interpret else LANES
        fwd = bwd = (min(block_n or bwd[0], _round_up(n, sub)),
                     min(block_v or bwd[1], _round_up(v, sub)))
    n_p = _round_up(n, math.lcm(fwd[0], bwd[0]))
    v_p = _round_up(v, math.lcm(fwd[1], bwd[1]))

    def prep(x, w, b, labels):
        xp = jnp.pad(x, ((0, n_p - n), (0, d_p - d)))
        wp = jnp.pad(w.astype(x.dtype), ((0, d_p - d), (0, v_p - v)))
        b2 = jnp.pad(b.astype(jnp.float32), (0, v_p - v)).reshape(1, v_p)
        lab2 = jnp.pad(labels, (0, n_p - n)).reshape(n_p, 1)
        return xp, wp, b2, lab2

    def run_fwd(x, w, b, labels):
        xp, wp, b2, lab2 = prep(x, w, b, labels)
        nll, lse = _fwd_call(xp, wp, b2, lab2, v, *fwd, interpret)
        return nll, lse, (xp, wp, b2, lab2, lse)

    def run_bwd(res, g_nll, g_lse=None):
        xp, wp, b2, lab2, lse = res
        goh = jnp.pad(g_nll.astype(jnp.float32),
                      (0, n_p - n)).reshape(n_p, 1)
        if g_lse is None:
            gp = goh          # plain CE: dlogits = g (softmax - onehot)
        else:
            # nll = lse - corr and d lse/d logits = softmax, so the lse
            # cotangent joins the softmax term: gp = g_nll + g_lse
            gp = goh + jnp.pad(g_lse.astype(jnp.float32),
                               (0, n_p - n)).reshape(n_p, 1)
        dx, dw, db = _bwd_call(xp, wp, b2, lab2, lse, gp, goh, v, *bwd,
                               interpret)
        return (dx[:n, :d].astype(xdt), dw[:v, :d].T.astype(wdt),
                db[0, :v].astype(bdt), None)

    if not with_lse:

        @jax.custom_vjp
        def fused(x, w, b, labels):
            nll, _, _ = run_fwd(x, w, b, labels)
            return nll[:n, 0]

        def fused_fwd(x, w, b, labels):
            nll, _, res = run_fwd(x, w, b, labels)
            return nll[:n, 0], res

        def fused_bwd(res, g):
            return run_bwd(res, g)

        fused.defvjp(fused_fwd, fused_bwd)
        return fused, fwd, bwd

    @jax.custom_vjp
    def fused_p(x, w, b, labels):
        nll, lse, _ = run_fwd(x, w, b, labels)
        return nll[:n, 0], lse[:n, 0]

    def fused_p_fwd(x, w, b, labels):
        nll, lse, res = run_fwd(x, w, b, labels)
        return (nll[:n, 0], lse[:n, 0]), res

    def fused_p_bwd(res, gs):
        return run_bwd(res, gs[0], gs[1])

    fused_p.defvjp(fused_p_fwd, fused_p_bwd)
    return fused_p, fwd, bwd


def _call(x, w, b, labels, block_n, block_v, interpret, **form):
    interpret = _should_interpret() if interpret is None else interpret
    f, fwd, bwd = _make_fused(tuple(x.shape), w.shape[1], x.dtype.name,
                              w.dtype.name, b.dtype.name, block_n, block_v,
                              interpret, **form)
    # once a traced call: which tiles ran, on the program_spans line
    obs.count("kernels.ce.fwd.%dx%d" % fwd)
    obs.count("kernels.ce.fused_bwd.%dx%d" % bwd)
    return f(x, w, b, labels)


def fused_linear_ce(x, w, b, labels, block_n=None, block_v=None,
                    interpret=None):
    """Per-token NLL of ``softmax(x @ w + b)`` at ``labels`` without
    materializing the (N, V) logits.  x: (N, d); w: (d, V); b: (V,);
    labels: (N,) int32.  Returns float32 (N,); differentiable in x/w/b.
    The shapes pick the tiles (:func:`_pick_tiles`); ``block_n`` and
    ``block_v`` are the tests' way to small ones."""
    return _call(x, w, b, labels, block_n, block_v, interpret)


def fused_linear_ce_partial(x, w, b, labels, block_n=None, block_v=None,
                            interpret=None):
    """Vocab-shard form: returns ``(nll_local, lse_local)`` over this
    shard's vocab slice (labels must be pre-localized; out-of-range labels
    — negative or >= V, including any landing inside the 128-padded vocab
    tail — match nothing, giving nll_local = lse_local).  Shards combine
    exactly:
    lse_g = logsumexp_c(lse_c), corr_g = sum_c(lse_c - nll_c),
    nll_g = lse_g - corr_g.  Differentiable in both outputs (the lse
    cotangent folds into the backward kernel's softmax term)."""
    return _call(x, w, b, labels, block_n, block_v, interpret,
                 with_lse=True)
