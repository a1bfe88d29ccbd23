"""Fused vocab-projection + softmax-cross-entropy as Pallas TPU kernels.

The (N, V) logits matrix — the largest tensor in an LM/NMT training step
(e.g. 16x512 tokens x 32k vocab = 1 GB in f32) — never reaches HBM: each
(block_n, block_v) logits tile is computed on the MXU from the resident
activation block and streamed through a running log-sum-exp, exactly the
flash-attention recipe applied to the classifier head.  The backward pass
recomputes each tile from the saved per-row lse and forms
``g * (softmax - onehot)`` on the fly for dx/dw/db.

Replaces the unfused pair RnnLinear -> SoftmaxDP (reference:
nmt/linear.cu + nmt/softmax_data_parallel.cu, which materialize the full
logits region between the two task launches) when the FFModel apply-time
fusion pass fires — see FFModel._lm_head_fusion.

Compiled via Mosaic on TPU; interpreter mode elsewhere (CPU test suite).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# forward: per-token nll = lse(x@w + b) - (x@w + b)[label]


def _fwd_kernel(x_ref, w_ref, b_ref, lab_ref, nll_ref, lse_ref,
                m_scr, l_scr, corr_scr, *, vocab, block_v):
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
    )(x, w, b2, lab2)


# ---------------------------------------------------------------------------
# backward: dlogits = g * (softmax - onehot); dx = dlogits @ wT,
# dw = xT @ dlogits, db = sum_rows(dlogits) — logits tiles recomputed


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


def _bwd_dx_kernel(x_ref, w_ref, b_ref, lab_ref, lse_ref, gp_ref, goh_ref,
                   dx_ref, dx_scr, *, vocab, block_v):
    vi = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        dx_scr[:] = jnp.zeros(dx_scr.shape, dx_scr.dtype)

    t = _tile_dlogits(x_ref, w_ref, b_ref, lab_ref, lse_ref, gp_ref,
                      goh_ref, vi * block_v, vocab)
    dx_scr[:] += jax.lax.dot_general(
        t.astype(w_ref.dtype), w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(vi == nv - 1)
    def _finish():
        dx_ref[:] = dx_scr[:].astype(dx_ref.dtype)


def _bwd_dw_kernel(x_ref, w_ref, b_ref, lab_ref, lse_ref, gp_ref, goh_ref,
                   dw_ref, db_ref, dw_scr, db_scr, *, vocab, block_v):
    ni = pl.program_id(1)
    nn = pl.num_programs(1)

    @pl.when(ni == 0)
    def _init():
        dw_scr[:] = jnp.zeros(dw_scr.shape, dw_scr.dtype)
        db_scr[:] = jnp.zeros(db_scr.shape, db_scr.dtype)

    t = _tile_dlogits(x_ref, w_ref, b_ref, lab_ref, lse_ref, gp_ref,
                      goh_ref, pl.program_id(0) * block_v, vocab)
    x = x_ref[:]
    dw_scr[:] += jax.lax.dot_general(
        x, t.astype(x.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    db_scr[:] += jnp.sum(t, axis=0, keepdims=True)

    @pl.when(ni == nn - 1)
    def _finish():
        dw_ref[:] = dw_scr[:].astype(dw_ref.dtype)
        db_ref[:] = db_scr[:].astype(db_ref.dtype)


def _bwd_call(x, w, b2, lab2, lse, gp2, goh2, vocab, block_n, block_v,
              interpret):
    n_p, d_p = x.shape
    v_p = w.shape[1]
    common = dict(vocab=vocab, block_v=block_v)
    # dx: token blocks outer, vocab innermost (accumulated in scratch)
    dx = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, **common),
        grid=(n_p // block_n, v_p // block_v),
        in_specs=[
            pl.BlockSpec((block_n, d_p), lambda i, j: (i, 0)),
            pl.BlockSpec((d_p, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, d_p), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_p, d_p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_n, d_p), jnp.float32)],
        interpret=interpret,
        name="ff_ce_bwd_dx",
    )(x, w, b2, lab2, lse, gp2, goh2)
    # dw/db: vocab blocks outer, token blocks innermost
    dw, db = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, **common),
        grid=(v_p // block_v, n_p // block_n),
        in_specs=[
            pl.BlockSpec((block_n, d_p), lambda j, i: (i, 0)),
            pl.BlockSpec((d_p, block_v), lambda j, i: (0, j)),
            pl.BlockSpec((1, block_v), lambda j, i: (0, j)),
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((d_p, block_v), lambda j, i: (0, j)),
            pl.BlockSpec((1, block_v), lambda j, i: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d_p, v_p), jnp.float32),
            jax.ShapeDtypeStruct((1, v_p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((d_p, block_v), jnp.float32),
            pltpu.VMEM((1, block_v), jnp.float32),
        ],
        interpret=interpret,
        name="ff_ce_bwd_dw",
    )(x, w, b2, lab2, lse, gp2, goh2)
    return dx, dw, db


# ---------------------------------------------------------------------------
# public op


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=None)
def _make_fused(x_shape, v, xdt, wdt, bdt, block_n, block_v, interpret,
                with_lse=False):
    n, d = x_shape
    if interpret:
        bn = min(block_n, _round_up(n, 8))
        bv = min(block_v, _round_up(v, 8))
        d_p = d
    else:
        bn = min(block_n, _round_up(n, 128))
        d_p = _round_up(d, 128)
        # the dw kernel holds a (d_p, bv) f32 accumulator plus double-
        # buffered (d_p, bv) weight blocks in VMEM — cap bv so large d
        # (e.g. NMT's 2048 hidden) stays under the ~16 MB scoped limit
        bv_cap = max(128, (2 * 1024 * 1024) // (d_p * 4) // 128 * 128)
        bv = min(block_v, bv_cap, _round_up(v, 128))
    n_p, v_p = _round_up(n, bn), _round_up(v, bv)

    def prep(x, w, b, labels):
        xp = jnp.pad(x, ((0, n_p - n), (0, d_p - d)))
        wp = jnp.pad(w.astype(x.dtype), ((0, d_p - d), (0, v_p - v)))
        b2 = jnp.pad(b.astype(jnp.float32), (0, v_p - v)).reshape(1, v_p)
        lab2 = jnp.pad(labels, (0, n_p - n)).reshape(n_p, 1)
        return xp, wp, b2, lab2

    def run_fwd(x, w, b, labels):
        xp, wp, b2, lab2 = prep(x, w, b, labels)
        nll, lse = _fwd_call(xp, wp, b2, lab2, v, bn, bv, interpret)
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
        dx, dw, db = _bwd_call(xp, wp, b2, lab2, lse, gp, goh, v, bn, bv,
                               interpret)
        return (dx[:n, :d].astype(xdt), dw[:d, :v].astype(wdt),
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
        return fused

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
    return fused_p


def fused_linear_ce(x, w, b, labels, block_n=256, block_v=512,
                    interpret=None):
    """Per-token NLL of ``softmax(x @ w + b)`` at ``labels`` without
    materializing the (N, V) logits.  x: (N, d); w: (d, V); b: (V,);
    labels: (N,) int32.  Returns float32 (N,); differentiable in x/w/b."""
    interpret = _should_interpret() if interpret is None else interpret
    f = _make_fused(tuple(x.shape), w.shape[1], x.dtype.name, w.dtype.name,
                    b.dtype.name, block_n, block_v, interpret)
    return f(x, w, b, labels)


def fused_linear_ce_partial(x, w, b, labels, block_n=256, block_v=512,
                            interpret=None):
    """Vocab-shard form: returns ``(nll_local, lse_local)`` over this
    shard's vocab slice (labels must be pre-localized; out-of-range labels
    — negative or >= V, including any landing inside the 128-padded vocab
    tail — match nothing, giving nll_local = lse_local).  Shards combine
    exactly:
    lse_g = logsumexp_c(lse_c), corr_g = sum_c(lse_c - nll_c),
    nll_g = lse_g - corr_g.  Differentiable in both outputs (the lse
    cotangent folds into the backward kernels' softmax term)."""
    interpret = _should_interpret() if interpret is None else interpret
    f = _make_fused(tuple(x.shape), w.shape[1], x.dtype.name, w.dtype.name,
                    b.dtype.name, block_n, block_v, interpret,
                    with_lse=True)
    return f(x, w, b, labels)
