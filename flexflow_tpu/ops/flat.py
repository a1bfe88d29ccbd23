"""Flat — the grid-transition op bridging the 4-D conv grid to the 2-D FC
grid.

Reference: flat.cu builds a projection region of Rect<2> values and a
``create_partition_by_image_range`` to derive the FC-side partition of the
flattened tensor (flat.cu:82-126).  On TPU this entire mechanism is a
reshape plus a sharding constraint on the result — GSPMD computes the
resharding (the "image" of the old partition under flattening) itself.

Layout note: activations are NHWC here, so flatten order is (h, w, c) rather
than the reference's NCHW (c, h, w); weights are initialized in this layout
so the model is equivalent up to a fixed permutation of FC input features.
"""

from __future__ import annotations

from typing import List

from flexflow_tpu.ops.base import Op, Tensor
from flexflow_tpu.strategy import ParallelConfig


class Flat(Op):
    AXIS_NAMES = ("c", "n")
    IS_VIEW = True

    def __init__(self, name: str, pc: ParallelConfig, input: Tensor):
        super().__init__(name, pc, [input])
        assert input.ndim == 4
        n, h, w, c = input.shape
        self.output = Tensor((n, h * w * c), input.dtype, self, name)

    def output_spec(self):
        from jax.sharding import PartitionSpec as P

        # features stay unsharded across 'c' (the FC grid's c-axis shards
        # *output* channels of the next linear, not flat's features)
        return P("n", None)

    def input_specs(self, pc=None):
        from jax.sharding import PartitionSpec as P

        return [P("n", None, None, None)]  # local reshape per batch shard

    def placement_signature(self):
        return ("flat",)

    def regrid_input_specs(self):
        from jax.sharding import PartitionSpec as P

        return [P("n", None, None, None)]

    def forward(self, params, state, xs: List, train: bool):
        (x,) = xs
        return x.reshape(x.shape[0], -1), state
