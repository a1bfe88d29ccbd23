"""Non-overlapping average-pool backward as a Pallas TPU kernel (+ plain
XLA forward).

Why this kernel exists: after the maxpool round, the pool family's
remaining "raw" (unvectorized) residue in the step profile is the AVG
side — Inception's global ``AveragePool 8x8`` tail over (8, 8, 2048) and
any stride==kernel tiling.  For exactly the *non-overlapping* geometries
(stride == kernel, padding 0 — which includes the global pool) every
input position belongs to one window, so the backward collapses from
XLA's padded window-transpose into a pure block upsample:

    dx[h, w] = dy[h // kh, w // kw] / (kh * kw)

one VMEM pass, no windows, no pad arithmetic.  The FORWARD stays plain
XLA (``reduce_window`` add is fully fusible — the maxpool lesson: a
standalone kernel forward loses the producer fusion, see
ops/pallas/maxpool.py).  The fused-ReLU variant masks dy by ``y > 0``
in-kernel from the pooled-output residual (OH x OW x C — tiny), so the
pool *input* never enters the VJP residuals.

Like maxpool, kernel operands are processed in **(H, W, C, N)** logical
order — N on lanes, C on sublanes; the bracketing transposes are layout
bitcasts on TPU for these N-minor conv activations — and the kernel runs
compiled via Mosaic on TPU, interpreter mode elsewhere so the CPU suite
exercises the identical code path (tests/test_pallas.py parity vs
lax.reduce_window autodiff).  Gated opt-in (FLEXFLOW_TPU_AVGPOOL=1,
ops.pallas.avgpool_enabled): an attribution candidate pending an
end-to-end TPU measurement — the maxpool experience says per-op wins can
vanish inside fusions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def supported(kh, kw, sh, sw, ph, pw, h, w, pool_type="avg") -> bool:
    """Static gate: unpadded geometries whose windows tile the input
    exactly — stride == kernel with no remainder rows, or the global
    pool (kernel == extent, any stride; the single window makes the
    stride irrelevant).  Everything else (overlap, remainders, padding)
    needs the count-of-valid-positions denominator and window-transpose
    scatter, and stays on the XLA path."""
    if pool_type != "avg" or (ph, pw) != (0, 0):
        return False
    if (kh, kw) == (h, w):
        return True  # global pool: one window, output 1x1
    return (sh, sw) == (kh, kw) and h % kh == 0 and w % kw == 0


def _ceil(a, b):
    return -(-a // b)


def _bwd_kernel(*refs, OH, OW, kh, kw, scale, relu):
    if relu:
        g_ref, y_ref, dx_ref = refs
    else:
        g_ref, dx_ref = refs
    g = g_ref[...].astype(jnp.float32)                 # (OH, OW, bc, bn)
    if relu:
        # compares run in f32 with full-array operands (the 32-bit
        # vector-compare constraint, see maxpool's module docstring)
        g = jnp.where(y_ref[...].astype(jnp.float32) > 0.0, g,
                      jnp.zeros_like(g))
    g = g * scale
    bc, bn = g.shape[2], g.shape[3]
    # block upsample: every input position is in exactly ONE window, so
    # dx is dy broadcast over the (kh, kw) tile — a major-dim broadcast +
    # reshape, both supported by Mosaic (no strided scatter)
    up = jnp.broadcast_to(g[:, None, :, None], (OH, kh, OW, kw, bc, bn))
    dx_ref[...] = up.reshape(OH * kh, OW * kw, bc, bn).astype(dx_ref.dtype)


def _pick_blocks(H, W, C, N):
    """N on lanes (128), C on sublanes; the dx block spans the full
    spatial extent (these geometries are small — the zoo's candidates
    are the 8x8 global tail and coarse tilings), so bc is budgeted to
    keep the block under the scoped-VMEM default."""
    bn = min(N, 128)
    cap = max(8, (6 * 1024 * 1024) // (H * W * bn * 4))
    return min(C, cap - cap % 8), bn


@functools.lru_cache(maxsize=None)
def _make_avgpool(shape, dtype_name, kh, kw, relu, interpret):
    N, H, W, C = shape
    OH, OW = H // kh, W // kw
    scale = 1.0 / float(kh * kw)
    bc, bn = _pick_blocks(H, W, C, N)
    gn, gc = _ceil(N, bn), _ceil(C, bc)

    bwd_kernel = functools.partial(_bwd_kernel, OH=OH, OW=OW, kh=kh, kw=kw,
                                   scale=scale, relu=relu)

    def bmap(ni, ci):
        return (0, 0, ci, ni)

    def bwd_call(gt, yt):
        dy_spec = pl.BlockSpec((OH, OW, bc, bn), bmap)
        return pl.pallas_call(
            bwd_kernel,
            grid=(gn, gc),
            in_specs=[dy_spec, dy_spec] if relu else [dy_spec],
            out_specs=pl.BlockSpec((H, W, bc, bn), bmap),
            out_shape=jax.ShapeDtypeStruct((H, W, C, N), gt.dtype),
            interpret=interpret,
            name="ff_avgpool_bwd",
        )(*((gt, yt) if relu else (gt,)))

    def fwd_xla(x):
        """Plain XLA: with padding 0 every window holds kh*kw valid
        positions, so the canonical sum/count divide is a constant
        scale.  Fully fusible — rides the producer fusions."""
        s = jax.lax.reduce_window(
            x, 0.0, jax.lax.add, (1, kh, kw, 1), (1, kh, kw, 1),
            ((0, 0),) * 4)
        y = s * jnp.asarray(scale, s.dtype)
        if relu:
            y = jax.nn.relu(y)
        # stored transposed so the backward reads it with N on lanes
        return y, jnp.transpose(y, (1, 2, 3, 0))

    @jax.custom_vjp
    def pool(x):
        return fwd_xla(x)[0]

    if relu:
        def pool_fwd(x):
            y, yt = fwd_xla(x)
            return y, (yt,)

        def pool_bwd(res, g):
            (yt,) = res
            gt = jnp.transpose(g, (1, 2, 3, 0))        # (OH, OW, C, N)
            return (jnp.transpose(bwd_call(gt, yt), (3, 0, 1, 2)),)
    else:
        def pool_fwd(x):
            return fwd_xla(x)[0], ()

        def pool_bwd(res, g):
            gt = jnp.transpose(g, (1, 2, 3, 0))
            return (jnp.transpose(bwd_call(gt, None), (3, 0, 1, 2)),)

    pool.defvjp(pool_fwd, pool_bwd)
    return pool


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def avgpool2d(x, kh, kw, sh, sw, ph, pw, relu=False, interpret=None):
    """Non-overlapping average pool (optionally fused ReLU) of NHWC
    ``x``; numerically identical to the canonical sum/count
    ``reduce_window`` pair under jax autodiff for the supported
    (exact-tiling) geometries."""
    n, h, w, c = x.shape
    assert supported(kh, kw, sh, sw, ph, pw, h, w)
    if (kh, kw) == (h, w):
        kh, kw = h, w  # global pool: stride is irrelevant, tile is H x W
    interpret = _should_interpret() if interpret is None else interpret
    f = _make_avgpool(tuple(x.shape), x.dtype.name, kh, kw,
                      bool(relu), interpret)
    return f(x)
