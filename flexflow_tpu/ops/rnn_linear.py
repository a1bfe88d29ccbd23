"""Vocab projection for RNN chunks (reference: nmt/linear.cu — 2-D (c, n)
grid over 3-D tensors: c shards the 20-32k vocab (tensor parallelism over
the projection), n shards batch; replica-grad + backward2 cross-shard
reduction nmt/linear.cu:413-446, here GSPMD's psum).  One weight shared by
all chunk ops (SharedVariable `linear` with bbox-ed per-GPU partial
gradients, nmt/rnn.cu:234-296 — here: jax.grad sums chunk contributions,
GSPMD reduces across shards)."""

from __future__ import annotations

from typing import Dict, List

from flexflow_tpu.ops.base import Op, Tensor
from flexflow_tpu.strategy import ParallelConfig


class RnnLinear(Op):
    AXIS_NAMES = ("c", "n")

    def __init__(self, name: str, pc: ParallelConfig, input: Tensor,
                 out_channels: int, param_key: str = None,
                 use_bias: bool = True):
        super().__init__(name, pc, [input])
        assert input.ndim == 3, "rnn linear input must be (batch, len, d)"
        n, length, d = input.shape
        self.in_channels = d
        self.out_channels = out_channels
        self.use_bias = bool(use_bias)
        if param_key:
            self.param_key = param_key
        self.output = Tensor((n, length, out_channels), "float32", self, name)

    def init_params(self, rng) -> Dict:
        import jax
        import jax.numpy as jnp

        kernel = jax.nn.initializers.glorot_uniform()(
            rng, (self.in_channels, self.out_channels), "float32")
        if not self.use_bias:
            return {"kernel": kernel}
        bias = jnp.zeros((self.out_channels,), "float32")
        return {"kernel": kernel, "bias": bias}

    def param_specs(self):
        from jax.sharding import PartitionSpec as P

        specs = {"kernel": P(None, "c"), "bias": P("c")}
        return specs if self.use_bias else {"kernel": specs["kernel"]}

    def output_spec(self):
        from jax.sharding import PartitionSpec as P

        return P("n", None, "c")

    def input_specs(self, pc=None):
        from jax.sharding import PartitionSpec as P

        return [P("n", None, None)]

    def regrid_input_specs(self):
        from jax.sharding import PartitionSpec as P

        return [P("n", None, None)]

    def placement_signature(self):
        if not self.use_bias:
            return (self.in_channels, self.out_channels, "no_bias")
        return (self.in_channels, self.out_channels)

    def head_operands(self, params, x):
        """The projection's input and its (d, vocabulary) matrix, as the
        plain and the fused head both read them."""
        return x, params["kernel"]

    def forward(self, params, state, xs: List, train: bool):
        import jax.numpy as jnp

        x, kernel = self.head_operands(params, xs[0])
        y = jnp.einsum("bld,dv->blv", x, kernel.astype(x.dtype),
                       preferred_element_type=jnp.float32)
        if self.use_bias:
            y = y + params["bias"]
        return y.astype(x.dtype), state

    def local_clone(self, pc: ParallelConfig):
        pc_, pn = pc.dims
        n, length, d = self.inputs[0].shape
        if n % pn or self.out_channels % pc_:
            return None
        t = Tensor((n // pn, length, d))
        return RnnLinear(self.name, ParallelConfig((1, 1), (0,)), t,
                         self.out_channels // pc_, use_bias=self.use_bias)

    def flops_per_sample(self) -> float:
        return 2.0 * self.output.shape[1] * self.in_channels * self.out_channels

    def param_bytes(self) -> int:
        return 4 * (self.in_channels * self.out_channels
                    + self.use_bias * self.out_channels)
