"""BatchNorm (reference: batch_norm.cu, cudnnBatchNormalizationForward
Training/Backward in SPATIAL mode; scale init 1.0, bias init 0.0,
batch_norm.cu:225-239).

Design divergence, on purpose: the reference computes batch statistics *per
task shard* (each Legion task calls cuDNN BN on its local slice — no
cross-shard sync), which makes training dynamics depend on the partition
grid.  We compute **global** batch statistics: ``jnp.mean`` over sharded
axes makes XLA insert the cross-shard reduction, i.e. sync-BN over the
{n,h,w} grid axes.  This preserves the framework's key invariant — identical
loss trajectories under any strategy (SURVEY.md §4) — which local BN breaks.
"""

from __future__ import annotations

from typing import Dict, List

from flexflow_tpu.ops.base import Op, Tensor
from flexflow_tpu.strategy import ParallelConfig


class BatchNorm(Op):
    AXIS_NAMES = ("w", "h", "c", "n")

    def __init__(self, name: str, pc: ParallelConfig, input: Tensor,
                 relu: bool = True, eps: float = 1e-5, momentum: float = 0.9):
        super().__init__(name, pc, [input])
        assert input.ndim == 4
        self.channels = input.shape[3]
        self.relu = relu
        self.eps = eps
        self.momentum = momentum
        self.output = Tensor(input.shape, input.dtype, self, name)

    def init_params(self, rng) -> Dict:
        import jax.numpy as jnp

        return {"scale": jnp.ones((self.channels,), "float32"),
                "bias": jnp.zeros((self.channels,), "float32")}

    def init_state(self) -> Dict:
        import jax.numpy as jnp

        return {"mean": jnp.zeros((self.channels,), "float32"),
                "var": jnp.ones((self.channels,), "float32")}

    def param_specs(self):
        from jax.sharding import PartitionSpec as P

        return {"scale": P("c"), "bias": P("c")}

    def output_spec(self):
        from jax.sharding import PartitionSpec as P

        return P("n", "h", "w", "c")

    def regrid_input_specs(self):
        from jax.sharding import PartitionSpec as P

        return [P("n", "h", "w", "c")]

    def input_specs(self, pc=None):
        from jax.sharding import PartitionSpec as P

        pc = pc or self.pc
        pw, ph, pcc, pn = pc.dims
        if pcc != 1:
            return None  # placed c-split would shard the running stats
        n, h, w, _ = self.inputs[0].shape
        if n % pn or h % ph or w % pw:
            return None
        return [P("n", "h", "w", None)]

    def placement_signature(self):
        # round 3: BatchNorm may join placement groups — its state is
        # threaded through run_group (state_specs) and its statistics are
        # grid-global via sharded_forward
        return (self.channels, self.relu, self.eps, self.momentum)

    def state_specs(self):
        from jax.sharding import PartitionSpec as P

        # per-channel running stats, replicated within the block (the
        # placed grid never splits c — input_specs rejects that)
        return {"mean": P(), "var": P()}

    def point_placeable(self) -> bool:
        # Set-family dispatch replicates the input, so GLOBAL batch
        # statistics need no collective — any batch/spatial grid
        # qualifies (round 5, closing the "BatchNorm on an irregular
        # list silently normalizes" gap).  c stays unsplit, matching
        # input_specs' reasoning (running stats shard with c).
        return self.pc.dims[2] == 1

    def point_forward(self, params, state, xs, idx, sizes, train):
        """One grid point from the FULL input: compute global batch
        statistics directly (every device holds the whole batch — the
        canonical semantics with zero collectives), update the running
        stats, normalize, and slice this point's output block."""
        import jax
        import jax.numpy as jnp

        from flexflow_tpu.ops.base import point_slice

        (x,) = xs
        if train:
            xf = x.astype("float32")
            mean = jnp.mean(xf, axis=(0, 1, 2))
            var = jnp.var(xf, axis=(0, 1, 2))
            m = self.momentum
            new_state = {"mean": m * state["mean"] + (1 - m) * mean,
                         "var": m * state["var"] + (1 - m) * var}
        else:
            mean, var = state["mean"], state["var"]
            new_state = dict(state)
        inv = jax.lax.rsqrt(var + self.eps) * params["scale"]
        shift = params["bias"] - mean * inv
        y = x * inv.astype(x.dtype) + shift.astype(x.dtype)
        if self.relu:
            y = jax.nn.relu(y)
        # the point's block: the slice fuses into the elementwise chain
        y = point_slice(y, self.output_spec(), sizes, idx)
        return (y,), new_state

    def placed_prelude(self, xs, train: bool):
        """Batch statistics over the WHOLE placed block, not the local
        shard: lax.pmean over the live grid axes keeps the framework
        invariant (identical loss trajectories under any strategy) that
        per-shard stats would break (the documented divergence from the
        reference's per-task cuDNN stats).  Runs outside the group switch
        (collectives are illegal inside branches)."""
        import jax.numpy as jnp
        from jax import lax

        live = tuple(name for name, size in
                     zip(self.AXIS_NAMES, self.pc.dims) if size > 1)
        if not live or not train:
            return None
        (x,) = xs
        xf = x.astype("float32")
        mean = lax.pmean(jnp.mean(xf, axis=(0, 1, 2)), live)
        mean2 = lax.pmean(jnp.mean(jnp.square(xf), axis=(0, 1, 2)), live)
        var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
        return mean, var

    def sharded_forward(self, params, state, xs, train: bool, aux=None):
        """Placed-grid forward: normalize with the block-global statistics
        from placed_prelude (collective-free branch body)."""
        import jax
        import jax.numpy as jnp

        if aux is None:
            return self.forward(params, state, xs, train)
        (x,) = xs
        mean, var = aux
        m = self.momentum
        state = {"mean": m * state["mean"] + (1 - m) * mean,
                 "var": m * state["var"] + (1 - m) * var}
        inv = jax.lax.rsqrt(var + self.eps) * params["scale"]
        shift = params["bias"] - mean * inv
        y = x * inv.astype(x.dtype) + shift.astype(x.dtype)
        if self.relu:
            y = jax.nn.relu(y)
        return y, state

    def forward(self, params, state, xs: List, train: bool):
        import jax
        import jax.numpy as jnp

        (x,) = xs
        if train:
            xf = x.astype("float32")
            mean = jnp.mean(xf, axis=(0, 1, 2))
            var = jnp.var(xf, axis=(0, 1, 2))
            m = self.momentum
            state = {"mean": m * state["mean"] + (1 - m) * mean,
                     "var": m * state["var"] + (1 - m) * var}
        else:
            mean, var = state["mean"], state["var"]
        # Fold stats+affine into per-channel scale/shift in fp32, then
        # normalize as ONE compute-dtype pass (y = x*inv + shift, ReLU
        # fused).  The training step is HBM-bound (measured 79% HBM util at
        # 33% MFU, batch 256); the previous fp32 elementwise chain made the
        # normalize+relu traffic — and the residuals its backward re-reads
        # — twice as wide as the activations.  Stats stay fp32 (the
        # reductions are read-only and cheap); per-channel vectors are tiny.
        inv = jax.lax.rsqrt(var + self.eps) * params["scale"]
        shift = params["bias"] - mean * inv
        y = x * inv.astype(x.dtype) + shift.astype(x.dtype)
        if self.relu:
            y = jax.nn.relu(y)
        return y, state

    def local_clone(self, pc: ParallelConfig):
        pw, ph, pc_, pn = pc.dims
        n, h, w, c = self.inputs[0].shape
        if n % pn or h % ph or w % pw or c % pc_:
            return None
        t = Tensor((n // pn, h // ph, w // pw, c // pc_))
        return BatchNorm(self.name, ParallelConfig((1, 1, 1, 1), (0,)), t,
                         self.relu, self.eps, self.momentum)

    def flops_per_sample(self) -> float:
        _, h, w, c = self.output.shape
        return 8.0 * h * w * c

    def param_bytes(self) -> int:
        return 4 * 2 * self.channels
