"""Pool2D (reference: pool_2d.cu, cudnnPoolingForward/Backward).

``lax.reduce_window`` max/avg in NHWC; the {w,h,c,n} grid shards the
activation, and XLA handles window halos under spatial partitioning.
Defaults mirror the reference API: ``pool2d(..., POOL_MAX, relu=True)``
(model.h:133-139, pool_2d.cu:50-56)."""

from __future__ import annotations

from typing import List

from flexflow_tpu.ops.base import Op, Tensor
from flexflow_tpu.strategy import ParallelConfig

POOL_MAX = "max"
POOL_AVG = "avg"


class Pool2D(Op):
    AXIS_NAMES = ("w", "h", "c", "n")

    def __init__(self, name: str, pc: ParallelConfig, input: Tensor,
                 kernel_h: int, kernel_w: int, stride_h: int, stride_w: int,
                 padding_h: int, padding_w: int,
                 pool_type: str = POOL_MAX, relu: bool = True):
        super().__init__(name, pc, [input])
        assert input.ndim == 4
        n, h, w, c = input.shape
        self.kernel_h, self.kernel_w = kernel_h, kernel_w
        self.stride_h, self.stride_w = stride_h, stride_w
        self.padding_h, self.padding_w = padding_h, padding_w
        self.pool_type = pool_type
        self.relu = relu
        out_h = 1 + (h + 2 * padding_h - kernel_h) // stride_h
        out_w = 1 + (w + 2 * padding_w - kernel_w) // stride_w
        self.output = Tensor((n, out_h, out_w, c), input.dtype, self, name)

    def output_spec(self):
        from jax.sharding import PartitionSpec as P

        return P("n", "h", "w", "c")

    def _spatial_placeable(self, pc) -> bool:
        """Placed spatial grids for AVG pools of the SAME/stride-1 family
        (Inception's in-block 3x3 pools): the halo prelude exchanges both
        the activation and a validity mask, reproducing the canonical
        count-of-valid-positions semantics exactly.  MAX pools are
        excluded from spatial placement (ppermute fills boundary halos
        with zeros, not -inf)."""
        pw, ph, pcc, pn = pc.dims
        if self.pool_type != POOL_AVG:
            return False
        n, h, w, _ = self.inputs[0].shape
        for parts, extent, k, s, p in (
                (ph, h, self.kernel_h, self.stride_h, self.padding_h),
                (pw, w, self.kernel_w, self.stride_w, self.padding_w)):
            if parts == 1:
                continue
            if s != 1 or k % 2 == 0 or p != (k - 1) // 2:
                return False
            if extent % parts or (k - 1) // 2 > extent // parts:
                return False
        return True

    def input_specs(self, pc=None):
        from jax.sharding import PartitionSpec as P

        pc = pc or self.pc
        pw, ph, pcc, pn = pc.dims
        n, _, _, c = self.inputs[0].shape
        cs = "c" if pcc > 1 else None
        if (pcc > 1 and c % pcc) or n % pn:
            return None
        if (pw, ph) == (1, 1):
            # batch (and optionally channel — pooling is per-channel)
            return [P("n", None, None, cs)]
        if self._spatial_placeable(pc):
            return [P("n", "h", "w", cs)]
        return None

    def placed_prelude(self, xs, train: bool):
        """Halo exchange for placed spatial AVG pools: the activation gets
        real neighbor halos (shared exchange_halo); the validity mask that
        reproduces the canonical count-of-valid-positions denominator is
        built LOCALLY from the shard's grid position (zero halo iff
        boundary shard) — no extra communication."""
        import jax.numpy as jnp
        from jax import lax

        from flexflow_tpu.ops.base import exchange_halo

        pw, ph, _pc, _pn = self.pc.dims
        if ph == 1 and pw == 1:
            return None
        (x,) = xs
        ones = jnp.ones_like(x)

        def mask_halo(t, axis_name, parts, k, dim):
            r = (k - 1) // 2
            if r == 0 or parts == 1:
                return t
            idx = lax.axis_index(axis_name)
            edge = lax.slice_in_dim(t, 0, r, axis=dim)
            lo = edge * (idx > 0).astype(t.dtype)
            hi = edge * (idx < parts - 1).astype(t.dtype)
            return jnp.concatenate([lo, t, hi], axis=dim)

        for axis_name, parts, k, dim in (("h", ph, self.kernel_h, 1),
                                         ("w", pw, self.kernel_w, 2)):
            x = exchange_halo(x, axis_name, parts, k, dim)
            ones = mask_halo(ones, axis_name, parts, k, dim)
        return x, ones

    def sharded_forward(self, params, state, xs, train: bool, aux=None):
        """Placed-grid forward: VALID avg pool over the pre-haloed
        activation, divided by the pre-haloed validity count."""
        import jax
        from jax import lax

        if aux is None:
            return self.forward(params, state, xs, train)
        x, ones = aux
        pw, ph, _pc, _pn = self.pc.dims
        pad_h = 0 if ph > 1 else self.padding_h
        pad_w = 0 if pw > 1 else self.padding_w
        window = (1, self.kernel_h, self.kernel_w, 1)
        strides = (1, self.stride_h, self.stride_w, 1)
        pads = ((0, 0), (pad_h, pad_h), (pad_w, pad_w), (0, 0))
        s = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        y = s / cnt
        if self.relu:
            y = jax.nn.relu(y)
        return y, state

    def placement_signature(self):
        return (self.kernel_h, self.kernel_w, self.stride_h, self.stride_w,
                self.padding_h, self.padding_w, self.pool_type, self.relu)

    def placed_local(self) -> bool:
        # point-local exactly when no spatial halos are needed
        pw, ph, _pc, _pn = self.pc.dims
        return pw == 1 and ph == 1

    def point_placeable(self) -> bool:
        # Set-family dispatch computes each point from the FULL
        # (replicated) input: halo rows are static slices, boundary
        # semantics are exact via fill values (-inf for MAX — lifting
        # the block/stride families' AVG-only restriction — zeros +
        # validity count for AVG).  Any stride/kernel/padding.
        return True

    def point_forward(self, params, state, xs, idx, sizes, train):
        """One grid point from the full input: pad with the pool's
        neutral fill, slice the fixed-size halo window, reduce VALID.
        AVG divides by the count of valid (un-padded) positions —
        identical to the canonical forward's semantics."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        (x,) = xs
        _, oh, ow, _ = self.output.shape
        pn, pcc = sizes.get("n", 1), sizes.get("c", 1)
        ph, pw = sizes.get("h", 1), sizes.get("w", 1)
        if pn > 1:
            bs = x.shape[0] // pn
            x = x[idx["n"] * bs:(idx["n"] + 1) * bs]
        if pcc > 1:
            cs = x.shape[3] // pcc
            x = x[..., idx["c"] * cs:(idx["c"] + 1) * cs]
        if ph == 1 and pw == 1:
            res, _ = self.forward(params, {}, [x], train)
            return (res,), {}
        pads2 = ((0, 0), (self.padding_h, self.padding_h),
                 (self.padding_w, self.padding_w), (0, 0))
        fill = -jnp.inf if self.pool_type == POOL_MAX else 0.0
        ones = jnp.pad(jnp.ones_like(x), pads2)
        x = jnp.pad(x, pads2, constant_values=fill)
        oh_l, ow_l = oh // ph, ow // pw
        h0 = idx["h"] * oh_l * self.stride_h
        hl = (oh_l - 1) * self.stride_h + self.kernel_h
        w0 = idx["w"] * ow_l * self.stride_w
        wl = (ow_l - 1) * self.stride_w + self.kernel_w
        x = x[:, h0:h0 + hl, w0:w0 + wl, :]
        ones = ones[:, h0:h0 + hl, w0:w0 + wl, :]
        window = (1, self.kernel_h, self.kernel_w, 1)
        strides = (1, self.stride_h, self.stride_w, 1)
        vp = ((0, 0),) * 4
        if self.pool_type == POOL_MAX:
            y = lax.reduce_window(x, -jnp.inf, lax.max, window, strides, vp)
        else:
            s = lax.reduce_window(x, 0.0, lax.add, window, strides, vp)
            cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, vp)
            y = s / cnt
        if self.relu:
            y = jax.nn.relu(y)
        return (y,), {}

    def regrid_input_specs(self):
        from jax.sharding import PartitionSpec as P

        return [P("n", "h", "w", "c")]

    def forward(self, params, state, xs: List, train: bool):
        import jax
        import jax.numpy as jnp
        from jax import lax

        (x,) = xs
        window = (1, self.kernel_h, self.kernel_w, 1)
        strides = (1, self.stride_h, self.stride_w, 1)
        pads = ((0, 0), (self.padding_h, self.padding_h),
                (self.padding_w, self.padding_w), (0, 0))
        if self.pool_type == POOL_MAX:
            y = lax.reduce_window(x, -jnp.inf, lax.max, window, strides, pads)
        else:
            # the count of valid (un-padded) positions depends only on
            # where the window sits in (H, W): one (1, H, W, 1) plane.  A
            # batch x channel sized plane of ones is the same numbers, and
            # XLA either constant-folds its reduce_window on the host
            # (about 95 s of every cold Inception-v3 b256 step compile on
            # a v5e host, PR 21) or recomputes it every step.
            ones = jnp.ones((1,) + x.shape[1:3] + (1,), x.dtype)
            s = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
            cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
            y = s / cnt
        if self.relu:
            y = jax.nn.relu(y)
        return y, state

    def local_clone(self, pc: ParallelConfig):
        pw, ph, pc_, pn = pc.dims
        n, h, w, c = self.inputs[0].shape
        if n % pn or h % ph or w % pw or c % pc_:
            return None
        t = Tensor((n // pn, h // ph, w // pw, c // pc_))
        return Pool2D(self.name, ParallelConfig((1, 1, 1, 1), (0,)), t,
                      self.kernel_h, self.kernel_w, self.stride_h,
                      self.stride_w, self.padding_h, self.padding_w,
                      self.pool_type, self.relu)

    def flops_per_sample(self) -> float:
        _, oh, ow, c = self.output.shape
        return float(oh * ow * c * self.kernel_h * self.kernel_w)
