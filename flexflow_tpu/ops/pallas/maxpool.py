"""Stride-2 max-pool backward as a Pallas TPU kernel (+ a selection-plane
forward in plain XLA).

Why this kernel exists: XLA lowers max-pool backward to
``select_and_scatter``, which the v5e profile classes as "raw"
(unvectorized) code — 5.0 ms of the measured 130 ms Inception step on the
two large pools alone — and whose unfusable operand forces a second
materialization of the pool inputs (examples/profiles/README.md).  The
reference leans on cuDNN for exactly this op (pool_2d.cu:214-218
cudnnPoolingBackward); this module beats XLA the same way the
flash-attention and fused-CE kernels do — by hand-scheduling VMEM.

Architecture (settled by per-op measurement of three full designs on the
compiled Inception step, round 4):

* The FORWARD is plain XLA: ``reduce_window`` for the max plus an
  elementwise fold over the k*k strided window slices producing ``sel``
  — the window-iteration-order rank of the first maximal element (the
  tie rule of select_and_scatter's GE select), sentinel where a fused
  ReLU clamps.  Every piece (pad/slice/compare/select) is fusible, so
  XLA melts the whole forward into neighboring fusions.  A Pallas
  forward (built and measured: 4.4 ms for the two big pools) loses
  ~1 ms/pool to exactly that fusion, and a backward that re-derives the
  argmax from x in-kernel (also built and measured: 7.2 ms) pays the
  x re-read plus the argmax arithmetic at dy-rate — SURVEY §7's
  "isolated timings mislead" warning, relearned with kernels.
* The BACKWARD is the Pallas kernel: reads dy + sel, writes dx — no x,
  no select_and_scatter (measured 2.9 ms vs 5.0 on the two big pools) —
  and the pool input drops out of the VJP residuals, removing its
  second materialization.
* Kernel operands are processed in **(H, W, C, N)** logical order so N
  rides the lane dimension and C the sublanes.  XLA already picks
  N-minor layouts (``{0,3,2,1}``) for these conv activations on TPU, so
  the transposes bracketing the kernel are layout bitcasts, not copies;
  and with the spatial dims in untiled (major) positions the stride-2
  scatter decomposition becomes pure reshapes (Mosaic supports splitting
  a major dim; it does NOT support strided slices, which lower to
  gathers).
* The H grid walks dx row-blocks with **VMEM carries**: each step keeps
  the previous dy/sel blocks (plus one-row tails) in scratch, so every
  HBM byte is read exactly once — no halo re-fetch.  The dx index map
  lags the grid by one block (a window reaches one row past its block);
  the hi=0 garbage block is overwritten at hi=1.
* Compares/selects run in f32 with full-array operands: the target has
  only 32-bit vector compares (neither bf16 cmpf nor int16 cmpi lower),
  and an i1 mask cannot be relayouted onto operands of another bitwidth
  nor onto broadcast-scalar branches.

Geometry support is the zoo's max pools (stride 2, k in {2,3}, pad in
{0,1}); ``Pool2D._use_pallas`` gates per layer.  On TPU the kernel
compiles via Mosaic; elsewhere it runs in interpreter mode so the CPU
test suite exercises the identical code path (tests/test_pallas.py
ties/geometry parity vs lax.reduce_window autodiff).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SENTINEL = 100.0  # sel value matching no window rank (e.g. ReLU-clamped)


def supported(kh, kw, sh, sw, ph, pw, pool_type="max") -> bool:
    """Static gate: exactly the geometries the parity tests pin down —
    the zoo's max pools (3x3/2 pad 0 or 1, 2x2/2 pad 0;
    pool_2d.cu:50-56 family).  Asymmetric kernels and 2x2/pad-1 would
    exercise untested offset arithmetic, so they stay on the XLA path."""
    return (pool_type == "max" and (sh, sw) == (2, 2) and kh == kw
            and ph == pw and (kh, ph) in ((3, 0), (3, 1), (2, 0)))


def _out_dim(size, k, p):
    return 1 + (size + 2 * p - k) // 2


# XLA's select_and_scatter runs "raw" (unvectorized): measured 5.0 ms on
# Inception's two big pools vs this kernel's 2.9 ms backward.  Expressed
# against the byte volumes below (XLA bwd moves 2.25x the input plane,
# the kernel bwd 1.5x), that A/B puts the raw path at ~1.15x the
# kernel's achieved bytes/s deficit — the calibration constant of the
# predictor.  (5.0/2.9) * (1.5/2.25) = 1.149.
_XLA_RAW_PENALTY = 1.15


def roofline_predicted_win_ms(n, h, w, c, kh, ph, dtype_bytes=2,
                              perf=None) -> float:
    """Predicted end-to-end win (ms, positive = kernel faster) of
    routing one pool layer through the Pallas backward, from the HBM
    roofline — the per-geometry cost model behind ``--pallas auto``
    (Pool2D._use_pallas), replacing the old ``min(h, w) >= 48`` guess.

    Honest accounting of BOTH sides of the measured round-4 trade:

    * XLA backward (select_and_scatter): reads x and dy, writes dx —
      ``2*x + dy`` bytes, at the raw-class bandwidth deficit
      (``_XLA_RAW_PENALTY``, calibrated from the 5.0 vs 2.9 ms A/B).
    * Kernel path: backward reads dy + sel and writes dx, PLUS the
      forward sel plane costs one extra pass over x (read x, write a
      bf16 sel) that XLA's fused reduce_window pipeline never pays —
      the term that made the end-to-end swap measure jitter-band
      neutral despite the 2x per-op win.

    With both sides priced, stride-2 pools come out slightly negative
    (the recorded measurement), so ``auto`` correctly declines what
    ``on`` can still force for measurement runs."""
    if perf is None:
        from flexflow_tpu.sim.cost_model import TpuChipPerf

        perf = TpuChipPerf()
    oh, ow = _out_dim(h, kh, ph), _out_dim(w, kh, ph)
    x_b = float(n * h * w * c * dtype_bytes)
    dy_b = float(n * oh * ow * c * dtype_bytes)
    sel_b = float(n * oh * ow * c * 2)          # sel is bf16 by design
    bw = perf.hbm_bandwidth
    xla_ms = (2 * x_b + dy_b) / bw * 1e3 * _XLA_RAW_PENALTY
    kernel_ms = (dy_b + sel_b + x_b) / bw * 1e3 \
        + (x_b + sel_b) / bw * 1e3              # fwd sel-plane pass
    return xla_ms - kernel_ms


def _offsets(kh, kw, ph, pw):
    """Static per-window-offset geometry: rank in window iteration order,
    the (row-pair shift, row parity) and (col shift, col parity) of input
    position 2t-p+j relative to window t."""
    out = []
    for jh in range(kh):
        qh, rh = divmod(jh - ph, 2)
        for jw in range(kw):
            qw, rw = divmod(jw - pw, 2)
            out.append((jh * kw + jw, qh, rh, qw, rw))
    return out


def _bwd_kernel(g_ref, s_ref, dx_ref, cg, cs, tg, ts,
                *, H, OH, W, OW, kh, kw, ph, pw, bh, bc, bn):
    hi = pl.program_id(2)
    dt = g_ref.dtype
    gcur, scur = g_ref[...], s_ref[...]                # (bh, OW, bc, bn)
    # compares/selects run uniformly in f32 (see module docstring); the
    # accumulators are f32 too, cast once at the dx store
    gwork = jnp.concatenate([tg[...], cg[...], gcur],
                            axis=0).astype(jnp.float32)
    swork = jnp.concatenate([ts[...], cs[...], scur],
                            axis=0).astype(jnp.float32)
    # output rows t in [(hi-1)bh - 1, (hi+1)bh) ; zero invalid rows' grads
    trow = bh * hi - bh - 1 + jax.lax.broadcasted_iota(
        jnp.int32, (2 * bh + 1, OW, bc, bn), 0)
    gwork = jnp.where((trow >= 0) & (trow < OH), gwork,
                      jnp.zeros_like(gwork))
    zpad = jnp.zeros((2 * bh + 1, 2, bc, bn), jnp.float32)
    spad = jnp.full((2 * bh + 1, 2, bc, bn), _SENTINEL, jnp.float32)
    gwork = jnp.concatenate([zpad, gwork, zpad], axis=1)
    swork = jnp.concatenate([spad, swork, spad], axis=1)

    W2 = (W + 1) // 2
    acc = [[jnp.zeros((bh, (W - rw + 1) // 2, bc, bn), jnp.float32)
            for rw in (0, 1)] for _ in (0, 1)]
    for rank, qh, rh, qw, rw in _offsets(kh, kw, ph, pw):
        Wr = (W - rw + 1) // 2
        rank_a = jnp.full(swork.shape, float(rank), jnp.float32)
        c = jnp.where(swork == rank_a, gwork, jnp.zeros_like(gwork))
        acc[rh][rw] = acc[rh][rw] + c[1 - qh:1 - qh + bh,
                                      2 - qw:2 - qw + Wr]
    rows = []
    for rh in (0, 1):
        even, odd = acc[rh]
        if odd.shape[1] < W2:
            odd = jnp.concatenate(
                [odd, jnp.zeros((bh, W2 - odd.shape[1], bc, bn),
                                jnp.float32)], axis=1)
        inter = jnp.stack([even, odd], axis=2).reshape(bh, 2 * W2, bc, bn)
        rows.append(inter[:, :W])
    dx = jnp.stack(rows, axis=1).reshape(2 * bh, W, bc, bn)
    dx_ref[...] = dx.astype(dt)

    tg[...] = cg[bh - 1:]
    ts[...] = cs[bh - 1:]
    cg[...] = gcur
    cs[...] = scur


def _pick_blocks(H, W, C, N, OH, itemsize):
    """Block sizes: N on lanes (128), C on sublanes, bh=2 — measured
    fastest on v5e across the zoo's pool shapes (147^2x64 .. 17^2x768);
    bh >= 2 also avoids a Mosaic relayout bug on size-1 leading dims."""
    bn = min(N, 128)
    bc = min(C, 32 if W < 96 else 32 // itemsize)
    return 2, bc, bn


def _ceil(a, b):
    return -(-a // b)


@functools.lru_cache(maxsize=None)
def _make_maxpool(shape, dtype_name, kh, kw, ph, pw, relu, interpret):
    N, H, W, C = shape
    dt = jnp.dtype(dtype_name)
    OH, OW = _out_dim(H, kh, ph), _out_dim(W, kw, pw)
    assert OH >= 1 and OW >= 1
    bh, bc, bn = _pick_blocks(H, W, C, N, OH, dt.itemsize)
    nxb, nyb = _ceil(H, 2 * bh), _ceil(OH, bh)
    gn, gc = _ceil(N, bn), _ceil(C, bc)

    # the pool1 working set (full-width rows + f32 compare temps) exceeds
    # the 16 MB scoped-vmem default; raise the cap for this kernel
    cparams = pltpu.CompilerParams(vmem_limit_bytes=48 * 1024 * 1024)

    bwd_kernel = functools.partial(
        _bwd_kernel, H=H, OH=OH, W=W, OW=OW, kh=kh, kw=kw, ph=ph, pw=pw,
        bh=bh, bc=bc, bn=bn)

    def dy_map(ni, ci, hi):
        return (jnp.minimum(hi, nyb - 1), 0, ci, ni)

    def dx_map(ni, ci, hi):
        return (jnp.maximum(hi - 1, 0), 0, ci, ni)

    def bwd_call(gt, sel, gdt):
        return pl.pallas_call(
            bwd_kernel,
            grid=(gn, gc, nxb + 1),
            in_specs=[pl.BlockSpec((bh, OW, bc, bn), dy_map),
                      pl.BlockSpec((bh, OW, bc, bn), dy_map)],
            out_specs=pl.BlockSpec((2 * bh, W, bc, bn), dx_map),
            out_shape=jax.ShapeDtypeStruct((H, W, C, N), gdt),
            scratch_shapes=[pltpu.VMEM((bh, OW, bc, bn), gdt),
                            pltpu.VMEM((bh, OW, bc, bn), jnp.bfloat16),
                            pltpu.VMEM((1, OW, bc, bn), gdt),
                            pltpu.VMEM((1, OW, bc, bn), jnp.bfloat16)],
            compiler_params=cparams,
            interpret=interpret,
            name="ff_maxpool_bwd",
        )(gt, sel)

    def fwd_xla(x):
        """y and the selection plane as plain XLA: reduce_window for the
        max, then an elementwise fold over the k*k strided window slices
        for the first-max rank.  Everything here is fusible (pad, strided
        slice, compare, select), so XLA melts it into the neighboring
        fusions — measured on the compiled Inception step, a standalone
        Pallas forward pass lost ~1 ms/pool to exactly this fusion."""
        m = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, kh, kw, 1), (1, 2, 2, 1),
            ((0, 0), (ph, ph), (pw, pw), (0, 0)))
        hi_h = 2 * (OH - 1) + kh  # padded extent the window slices reach
        hi_w = 2 * (OW - 1) + kw
        xp = jnp.pad(x, ((0, 0), (ph, max(0, hi_h - H - ph)),
                         (pw, max(0, hi_w - W - pw)), (0, 0)),
                     constant_values=-jnp.inf)
        sel = jnp.full(m.shape, _SENTINEL, jnp.float32)
        mf = m.astype(jnp.float32)
        for jh in range(kh):
            for jw in range(kw):
                sl = jax.lax.slice(
                    xp, (0, jh, jw, 0),
                    (xp.shape[0], jh + 2 * (OH - 1) + 1,
                     jw + 2 * (OW - 1) + 1, xp.shape[3]),
                    (1, 2, 2, 1))
                rank = float(jh * kw + jw)
                # first max == min rank among maxima (ranks ascend in
                # window iteration order — XLA select_and_scatter's GE
                # tie rule)
                sel = jnp.minimum(
                    sel, jnp.where(sl.astype(jnp.float32) == mf,
                                   rank, _SENTINEL))
        if relu:
            sel = jnp.where(mf > 0, sel, _SENTINEL)
            m = jnp.maximum(m, jnp.zeros_like(m))
        # sel is stored transposed so the backward kernel reads it with N
        # on lanes, like its dy operand
        return m, jnp.transpose(sel.astype(jnp.bfloat16), (1, 2, 3, 0))

    @jax.custom_vjp
    def pool(x):
        y, _ = fwd_xla(x)
        return y

    def pool_fwd(x):
        y, sel = fwd_xla(x)
        return y, (sel,)

    def pool_bwd(res, g):
        (sel,) = res
        gt = jnp.transpose(g, (1, 2, 3, 0))            # (OH, OW, C, N)
        dxt = bwd_call(gt, sel, gt.dtype)
        return (jnp.transpose(dxt, (3, 0, 1, 2)),)

    pool.defvjp(pool_fwd, pool_bwd)
    return pool


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def maxpool2d(x, kh, kw, ph, pw, relu=False, interpret=None):
    """Stride-2 max pool (optionally fused ReLU) of NHWC ``x``; numerically
    identical — including gradient tie-breaking — to
    ``relu(lax.reduce_window(x, -inf, max, (1,kh,kw,1), (1,2,2,1), pad))``
    under jax autodiff (up to bf16 summation order for inputs that receive
    gradient from several overlapping windows)."""
    assert supported(kh, kw, 2, 2, ph, pw)
    interpret = _should_interpret() if interpret is None else interpret
    f = _make_maxpool(tuple(x.shape), x.dtype.name, kh, kw, ph, pw,
                      bool(relu), interpret)
    return f(x)
