"""Flash attention as Pallas TPU kernels (forward + custom-VJP backward).

The O(S^2) score matrix never leaves VMEM: the kernel streams K/V blocks
through the MXU against a resident Q block, maintaining the numerically
stable running max / denominator (same math as
parallel/ring_attention._stream_block, which is the XLA fallback path).
Backward is the standard flash recomputation: softmax probabilities are
rebuilt per tile from the saved log-sum-exp, so residual memory is O(S)
per row (out + lse) instead of O(S^2).

Layout/tiling (per /opt/skills/guides/pallas_guide.md): grid = (batch*heads,
S_q/block_q, S_k/block_k) with the K dimension innermost, so the
(block_q, d) output block is revisited across K steps and accumulated in
f32 VMEM scratch; blocks default to 512x512 score tiles (measured fastest
on v5e; clamped down for short sequences, always 128-aligned); the running
max/denominator live in (block_q, 128)-lane scratch; per-row lse/delta are
carried as (S, 1) column tensors so no lane<->sublane relayout is needed.
Causal tiles strictly above the diagonal skip their matmuls entirely.

On TPU the kernels compile via Mosaic; elsewhere they run in interpreter
mode, so the identical code path is exercised by the CPU test suite.

This is the framework's hand-written-kernel layer — the role the CUDA leaf
tasks play in the reference (e.g. conv_2d.cu:523-536), applied to the one
op family the reference lacks (attention, SURVEY.md §2.6) where manual VMEM
scheduling beats XLA.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 512
_NEG_INF = float("-inf")


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _block_mask(q_off, k_off, shape, sk: int, causal: bool):
    """Validity mask for one (block_q, block_k) score tile: mask padded K
    columns (kpos >= sk) and, when causal, future positions."""
    kpos = k_off + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    valid = kpos < sk
    if causal:
        qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        valid = jnp.logical_and(valid, qpos >= kpos)
    return valid


# ---------------------------------------------------------------------------
# forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, sk, block_q, block_k):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    q_off = pl.program_id(1) * block_q
    k_off = ki * block_k

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, m_scr.dtype)
        l_scr[:] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[:] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    live = q_off + block_q - 1 >= k_off if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = _block_mask(q_off, k_off, s.shape, sk, causal)
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # fully-masked rows keep m = -inf; exp(-inf - -inf) would be nan
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(valid, jnp.exp(s - safe_m), 0.0)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)
        l_new = l_scr[:, 0:1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        m = m_scr[:, 0:1]
        l = jnp.maximum(l_scr[:, 0:1], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(jnp.isfinite(m), m + jnp.log(l), _NEG_INF)


def _fwd_call(q, k, v, scale, causal, sk, block_q, block_k, interpret):
    """sk is the UNPADDED key length (mask bound); array shapes are padded."""
    bh, sq, d = q.shape
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               sk=sk, block_q=block_q, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // block_q, k.shape[1] // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="ff_flash_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward: recompute p per tile from saved lse; delta = rowsum(do * o)


def _p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_off, k_off,
          scale, sk, causal):
    """Recompute probabilities p and score-gradient ds for one tile."""
    q = q_ref[0]
    k = k_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    valid = _block_mask(q_off, k_off, s.shape, sk, causal)
    lse = lse_ref[0]                     # (block_q, 1)
    safe_lse = jnp.where(jnp.isfinite(lse), lse, 0.0)
    p = jnp.where(valid, jnp.exp(s - safe_lse), 0.0)
    do = do_ref[0]
    dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0]) * scale
    return p, ds, do, q


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, sk, block_q, block_k):
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    q_off = qi * block_q
    k_off = pl.program_id(1) * block_k

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, dk_scr.dtype)
        dv_scr[:] = jnp.zeros(dv_scr.shape, dv_scr.dtype)

    live = q_off + block_q - 1 >= k_off if causal else True

    @pl.when(live)
    def _compute():
        p, ds, do, q = _p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                             q_off, k_off, scale, sk, causal)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *, scale, causal, sk, block_q, block_k):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    q_off = pl.program_id(1) * block_q
    k_off = ki * block_k

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros(dq_scr.shape, dq_scr.dtype)

    live = q_off + block_q - 1 >= k_off if causal else True

    @pl.when(live)
    def _compute():
        _, ds, _, _ = _p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            q_off, k_off, scale, sk, causal)
        k = k_ref[0]
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_call(q, k, v, do, lse, delta, scale, causal, sk, block_q, block_k,
              interpret):
    """sk is the UNPADDED key length (mask bound); array shapes are padded."""
    bh, sq, d = q.shape
    sk_p = k.shape[1]
    common = dict(scale=scale, causal=causal, sk=sk,
                  block_q=block_q, block_k=block_k)
    # dk/dv: K blocks outer, Q innermost (accumulated across Q in scratch)
    dkv_spec = [
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),   # q
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),   # k
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),   # v
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),   # do
        pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),   # lse
        pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),   # delta
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(bh, sk_p // block_k, sq // block_q),
        in_specs=dkv_spec,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, jnp.float32),
            jax.ShapeDtypeStruct(v.shape, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="ff_flash_bwd_dkv",
    )(q, k, v, do, lse, delta)
    dq_spec = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
    ]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(bh, sq // block_q, sk_p // block_k),
        in_specs=dq_spec,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="ff_flash_bwd_dq",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op: (B, H, S, d) -> (B, H, Sq, d) float32, differentiable


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=None)
def _make_flash(q_shape, k_shape, qdt, kdt, vdt, causal, block_q, block_k,
                interpret, with_lse=False):
    """Build a custom-VJP flash op specialized for one static configuration
    (shapes/dtypes/blocks are Python constants closed over by the kernels;
    the VJP residuals are pure arrays).

    With ``with_lse`` the op returns ``(out, lse)`` — the *partial*
    attention form used by ring/context parallelism, where per-chunk
    results are merged by log-sum-exp weighting.  The lse cotangent folds
    into the backward kernels for free: d lse/d s_ij = p_ij, so passing
    ``delta - g_lse`` where the kernels expect ``delta`` yields
    ds = p (dp - delta + g_lse) — no kernel changes."""
    b, h, sq, d = q_shape
    sk = k_shape[2]
    scale = 1.0 / math.sqrt(d)
    if interpret:
        bq = min(block_q, _round_up(sq, 8))
        bk = min(block_k, _round_up(sk, 8))
        d_p = d
    else:
        # on hardware, lane dims (d) want full 128 tiles; clamp blocks so a
        # short sequence is not padded all the way to the default block
        bq = min(block_q, _round_up(sq, 128))
        bk = min(block_k, _round_up(sk, 128))
        d_p = _round_up(d, 128)
    sq_p, sk_p = _round_up(sq, bq), _round_up(sk, bk)

    def prep(x, s_p):
        # (B,H,S,d) -> (B*H, S_pad, d_pad); zero d-columns do not change
        # scores, padded K rows are masked via sk, padded Q rows sliced off
        x = x.reshape(b * h, x.shape[2], d)
        return jnp.pad(x, ((0, 0), (0, s_p - x.shape[1]), (0, d_p - d)))

    def run_fwd(q, k, v):
        qp, kp, vp = prep(q, sq_p), prep(k, sk_p), prep(v, sk_p)
        out, lse = _fwd_call(qp, kp, vp, scale, causal, sk, bq, bk, interpret)
        return out, lse, (qp, kp, vp, lse, out)

    def run_bwd(res, g, g_lse=None):
        qp, kp, vp, lse, out = res
        do = jnp.pad(g.astype(jnp.float32).reshape(b * h, sq, d),
                     ((0, 0), (0, sq_p - sq), (0, d_p - d)))
        do_k = do.astype(qdt)  # kernel operand in the primal compute dtype
        # delta is zero on padded Q rows (do = 0 there), so they contribute
        # nothing to dk/dv even though their lse is arbitrary
        delta = jnp.sum(do * out, axis=-1, keepdims=True)
        if g_lse is not None:
            glse_p = jnp.pad(g_lse.astype(jnp.float32).reshape(b * h, sq, 1),
                             ((0, 0), (0, sq_p - sq), (0, 0)))
            delta = delta - glse_p  # ds = p (dp - delta + g_lse)
        dq, dk, dv = _bwd_call(qp, kp, vp, do_k, lse, delta, scale, causal,
                               sk, bq, bk, interpret)
        return (dq[:, :sq, :d].reshape(b, h, sq, d).astype(qdt),
                dk[:, :sk, :d].reshape(b, h, sk, d).astype(kdt),
                dv[:, :sk, :d].reshape(b, h, sk, d).astype(vdt))

    if not with_lse:

        @jax.custom_vjp
        def flash(q, k, v):
            out, _, _ = run_fwd(q, k, v)
            return out[:, :sq, :d].reshape(b, h, sq, d)

        def flash_fwd(q, k, v):
            out, _, res = run_fwd(q, k, v)
            return out[:, :sq, :d].reshape(b, h, sq, d), res

        def flash_bwd(res, g):
            return run_bwd(res, g)

        flash.defvjp(flash_fwd, flash_bwd)
        return flash

    def unpack(out, lse):
        return (out[:, :sq, :d].reshape(b, h, sq, d),
                lse[:, :sq, 0].reshape(b, h, sq))

    @jax.custom_vjp
    def flash_p(q, k, v):
        out, lse, _ = run_fwd(q, k, v)
        return unpack(out, lse)

    def flash_p_fwd(q, k, v):
        out, lse, res = run_fwd(q, k, v)
        return unpack(out, lse), res

    def flash_p_bwd(res, gs):
        g, g_lse = gs
        return run_bwd(res, g, g_lse)

    flash_p.defvjp(flash_p_fwd, flash_p_bwd)
    return flash_p


def flash_attention(q, k, v, causal=False, block_q=DEFAULT_BLOCK,
                    block_k=DEFAULT_BLOCK, interpret=None):
    """softmax(q kᵀ / sqrt(d) [+ causal mask]) v without materializing the
    score matrix.  q, k, v: (B, H, S, d); returns float32 (B, H, Sq, d)."""
    interpret = _should_interpret() if interpret is None else interpret
    f = _make_flash(tuple(q.shape), tuple(k.shape), q.dtype.name,
                    k.dtype.name, v.dtype.name, bool(causal), block_q,
                    block_k, interpret)
    return f(q, k, v)


def flash_attention_partial(q, k, v, causal=False, block_q=DEFAULT_BLOCK,
                            block_k=DEFAULT_BLOCK, interpret=None):
    """Partial attention over one K/V chunk: returns ``(out, lse)`` where
    ``out`` is the chunk-normalized attention and ``lse`` (B, H, Sq) the
    log-sum-exp of its scores.  Chunks merge exactly via
    :func:`combine_partials` — the building block of the Pallas ring-
    attention path (each ring step attends Q against the resident K/V
    block, then results merge by lse weight).  Differentiable in both
    outputs."""
    interpret = _should_interpret() if interpret is None else interpret
    f = _make_flash(tuple(q.shape), tuple(k.shape), q.dtype.name,
                    k.dtype.name, v.dtype.name, bool(causal), block_q,
                    block_k, interpret, with_lse=True)
    return f(q, k, v)


def combine_partials(o1, lse1, o2, lse2):
    """Merge two chunk-normalized partial attentions by log-sum-exp weight:
    softmax over the union of their key sets.  Fully-masked partials
    (lse = -inf, o = 0) drop out; if both are masked the result is 0."""
    m = jnp.maximum(lse1, lse2)
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    w1 = jnp.where(jnp.isfinite(lse1), jnp.exp(lse1 - safe_m), 0.0)
    w2 = jnp.where(jnp.isfinite(lse2), jnp.exp(lse2 - safe_m), 0.0)
    tot = w1 + w2
    lse = jnp.where(tot > 0, safe_m + jnp.log(jnp.maximum(tot, 1e-30)),
                    _NEG_INF)
    denom = jnp.maximum(tot, 1e-30)[..., None]
    o = (o1 * w1[..., None] + o2 * w2[..., None]) / denom
    return o, lse
