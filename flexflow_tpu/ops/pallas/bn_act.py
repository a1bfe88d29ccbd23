"""Fused batch-norm normalize + ReLU as a Pallas TPU kernel pair.

Why this kernel exists: BatchNorm.forward already folds the statistics
and affine into per-channel (inv, shift) f32 vectors and runs the
normalize as ONE compute-dtype elementwise pass (the HBM-bound fold,
ops/norm.py).  What XLA cannot be told is how to schedule the BACKWARD:
the VJP of ``relu(x * inv + shift)`` needs dx plus two per-channel
reductions (d_inv = sum(dy*x), d_shift = sum(dy)), and the profile shows
the reductions splitting off the elementwise producer into separate
passes over x and dy.  The kernel here emits all three outputs from a
single VMEM pass per block — x and dy are read exactly once — with the
ReLU mask recomputed from (x, inv, shift) so the activation ``y`` never
enters the residuals.

Layout: operands are flattened to (M, C) with C on lanes — the natural
C-minor layout of NHWC activations, so the reshape is free — and the
channel vectors ride (1, C) blocks (the TPU 2-D operand requirement).
The grid walks channel blocks outer, row blocks inner; the per-channel
sums accumulate across the inner (sequential) grid steps into a
revisited (1, C) output block.  All math is f32 (32-bit vector
compares), cast once at the stores.

Runs compiled via Mosaic on TPU, interpreter mode elsewhere so the CPU
suite exercises the identical path (tests/test_pallas.py parity vs the
unfused XLA chain under autodiff).  Gated opt-in (FLEXFLOW_TPU_BNRELU=1,
ops.pallas.bnrelu_enabled): an attribution candidate pending an
end-to-end TPU measurement, same honesty bar as maxpool.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pick_bm(M):
    """Largest power-of-two row block (>= 8 sublanes) dividing M."""
    for bm in (1024, 512, 256, 128, 64, 32, 16, 8):
        if M % bm == 0:
            return bm
    return None


def supported(n, h, w, c) -> bool:
    """Static gate: the flattened row count must split into whole row
    blocks — out-of-bounds rows would pollute the channel-sum
    accumulators, so ragged M is refused rather than masked.  (Ragged C
    is fine: garbage lanes stay in garbage lanes and are cropped at the
    store.)"""
    return _pick_bm(n * h * w) is not None


def _ceil(a, b):
    return -(-a // b)


def _fwd_kernel(x_ref, inv_ref, shift_ref, y_ref, *, relu):
    y = x_ref[...].astype(jnp.float32) * inv_ref[...] + shift_ref[...]
    if relu:
        y = jnp.maximum(y, 0.0)
    y_ref[...] = y.astype(y_ref.dtype)


def _bwd_kernel(x_ref, inv_ref, shift_ref, g_ref, dx_ref, dinv_ref,
                dshift_ref, *, relu):
    mi = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)                 # (bm, bc)
    g = g_ref[...].astype(jnp.float32)
    inv = inv_ref[...]                                 # (1, bc) f32
    if relu:
        # mask recomputed from the residuals — y never materializes
        pre = x * inv + shift_ref[...]
        g = jnp.where(pre > 0.0, g, jnp.zeros_like(g))
    dx_ref[...] = (g * inv).astype(dx_ref.dtype)
    dinv_p = jnp.sum(g * x, axis=0, keepdims=True)
    dshift_p = jnp.sum(g, axis=0, keepdims=True)

    # the (1, bc) sum blocks are revisited across the inner (row) grid
    # steps — sequential on TPU — accumulating the partials in place
    @pl.when(mi == 0)
    def _init():
        dinv_ref[...] = dinv_p
        dshift_ref[...] = dshift_p

    @pl.when(mi > 0)
    def _acc():
        dinv_ref[...] += dinv_p
        dshift_ref[...] += dshift_p


@functools.lru_cache(maxsize=None)
def _make_bn_act(M, C, dtype_name, relu, interpret):
    dt = jnp.dtype(dtype_name)
    bm = _pick_bm(M)
    assert bm is not None
    bc = min(C, 128)
    gm, gc = M // bm, _ceil(C, bc)

    fwd_kernel = functools.partial(_fwd_kernel, relu=relu)
    bwd_kernel = functools.partial(_bwd_kernel, relu=relu)

    def xmap(ci, mi):
        return (mi, ci)

    def cmap(ci, mi):
        return (0, ci)

    x_spec = pl.BlockSpec((bm, bc), xmap)
    c_spec = pl.BlockSpec((1, bc), cmap)

    def fwd_call(x2, inv2, shift2):
        return pl.pallas_call(
            fwd_kernel,
            grid=(gc, gm),
            in_specs=[x_spec, c_spec, c_spec],
            out_specs=x_spec,
            out_shape=jax.ShapeDtypeStruct((M, C), dt),
            interpret=interpret,
            name="ff_bn_act_fwd",
        )(x2, inv2, shift2)

    def bwd_call(x2, inv2, shift2, g2):
        return pl.pallas_call(
            bwd_kernel,
            grid=(gc, gm),
            in_specs=[x_spec, c_spec, c_spec, x_spec],
            out_specs=[x_spec, c_spec, c_spec],
            out_shape=[jax.ShapeDtypeStruct((M, C), dt),
                       jax.ShapeDtypeStruct((1, C), jnp.float32),
                       jax.ShapeDtypeStruct((1, C), jnp.float32)],
            interpret=interpret,
            name="ff_bn_act_bwd",
        )(x2, inv2, shift2, g2)

    @jax.custom_vjp
    def f(x2, inv2, shift2):
        return fwd_call(x2, inv2, shift2)

    def f_fwd(x2, inv2, shift2):
        return fwd_call(x2, inv2, shift2), (x2, inv2, shift2)

    def f_bwd(res, g2):
        x2, inv2, shift2 = res
        return bwd_call(x2, inv2, shift2, g2)

    f.defvjp(f_fwd, f_bwd)
    return f


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def bn_act(x, inv, shift, relu=True, interpret=None):
    """Fused per-channel scale-shift(-ReLU) of NHWC ``x``:
    ``relu(x * inv + shift)`` with a one-pass backward producing dx and
    both per-channel sums.  ``inv``/``shift`` are the folded f32 (C,)
    vectors from BatchNorm.forward; gradients flow back to them (and
    through them to scale/bias/mean/var) via jax autodiff of the fold."""
    n, h, w, c = x.shape
    assert supported(n, h, w, c)
    interpret = _should_interpret() if interpret is None else interpret
    f = _make_bn_act(n * h * w, c, x.dtype.name, bool(relu), interpret)
    y2 = f(x.reshape(n * h * w, c),
           inv.astype(jnp.float32).reshape(1, c),
           shift.astype(jnp.float32).reshape(1, c))
    return y2.reshape(x.shape)
