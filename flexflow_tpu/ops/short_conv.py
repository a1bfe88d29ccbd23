"""The gated short convolution (the ``conv`` layers of ``lfm2`` and
``lfm2_moe`` configurations; LFM2-8B-A1B has eighteen of them to six
attention layers): one product in, a depthwise causal convolution of a few
taps between two elementwise gates, one product out.

    [B | C | X] = x W_in                     (d -> 3 d, thirds in that order)
    u_t = B_t * X_t
    v_t[c] = sum_j w[c, j] u_{t-K+1+j}[c]    (K taps, zeros before the
                                              sequence starts, no bias,
                                              no activation)
    y = (C * v) W_out                        (d -> d)

It shares :func:`ops.ssm.causal_conv1d` with the Mamba-2 mixer's ``SSMIn``
(four taps and a bias there, three and none here).  The two products take
the compute type with float32 accumulation; the gates and the convolution
between them run in float32 on the rounded thirds and are rounded once,
where ``W_out`` reads them, so XLA fuses them into one pass over
``[B | C | X]``.  Plain XLA on every backend: a traced layer counts
``kernels.short_conv.xla.<channels>x<taps>`` (the name says which form
ran, as the other operators' do; there is no other form yet) and sets the
level ``conv.taps``.

Grid ('s', 'n') as the other sequence operators', of which only (1, 1)
is implemented: a split sequence would hand the last ``K - 1`` positions
between shards.
"""

from __future__ import annotations

import math
from typing import Dict, List

from flexflow_tpu import obs
from flexflow_tpu.ops.base import Tensor
from flexflow_tpu.ops.seq_common import _SeqElementwise
from flexflow_tpu.ops.ssm import causal_conv1d
from flexflow_tpu.strategy import ParallelConfig


def gated_short_conv(x, w_in, conv_w, w_out):
    """x (B, S, d) -> (B, S, d_out): the equations above."""
    import jax.numpy as jnp

    d = conv_w.shape[0]
    proj = jnp.einsum("bsd,de->bse", x, w_in.astype(x.dtype),
                      preferred_element_type=jnp.float32).astype(x.dtype)
    f32 = jnp.float32
    b, c, xx = (proj[..., i * d:(i + 1) * d].astype(f32) for i in range(3))
    gated = (c * causal_conv1d(b * xx, conv_w, None)).astype(x.dtype)
    return jnp.einsum("bse,ed->bsd", gated, w_out.astype(x.dtype),
                      preferred_element_type=jnp.float32).astype(x.dtype)


class GatedShortConv(_SeqElementwise):
    def __init__(self, name: str, pc: ParallelConfig, input: Tensor,
                 taps: int):
        super().__init__(name, pc, [input])
        assert input.ndim == 3
        self.d = input.shape[2]
        self.taps = int(taps)
        if self.taps < 1:
            raise ValueError(f"op {name!r}: {taps} taps")
        self.output = Tensor(input.shape, input.dtype, self, name)

    def init_params(self, rng) -> Dict:
        """The two matrices glorot uniform; the convolution as
        ``nn.Conv1d`` starts it (uniform within 1/sqrt(K), a channel its
        own fan-in), as ``SSMIn``'s."""
        import jax

        k_in, k_conv, k_out = jax.random.split(rng, 3)
        init = jax.nn.initializers.glorot_uniform()
        bound = 1.0 / math.sqrt(self.taps)
        return {"w_in": init(k_in, (self.d, 3 * self.d), "float32"),
                "conv_w": jax.random.uniform(
                    k_conv, (self.d, self.taps), "float32", -bound, bound),
                "w_out": init(k_out, (self.d, self.d), "float32")}

    def param_specs(self):
        from jax.sharding import PartitionSpec as P

        return {"w_in": P(None, None), "conv_w": P(None, None),
                "w_out": P(None, None)}

    def validate_partitioning(self):
        super().validate_partitioning()
        if any(p != 1 for p in self.pc.dims):
            raise ValueError(
                f"op {self.name!r}: the gated short convolution runs on "
                f"the grid (1, 1) only; {self.pc.dims} (sequence or batch "
                f"parts) is not implemented")

    def forward(self, params, state, xs: List, train: bool):
        obs.count(f"kernels.short_conv.xla.{self.d}x{self.taps}")
        obs.count("conv.taps", self.taps, level=True)
        return gated_short_conv(xs[0], params["w_in"], params["conv_w"],
                                params["w_out"]), state

    def cost_signature(self) -> tuple:
        return (self.taps,)

    def flops_per_sample(self) -> float:
        s, d = self.output.shape[1], self.d
        return s * (2.0 * d * 4 * d + (2.0 * self.taps + 2.0) * d)

    def param_bytes(self) -> int:
        return 4 * (4 * self.d * self.d + self.d * self.taps)
