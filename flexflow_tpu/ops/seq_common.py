"""Sequence-model elementwise ops: LayerNorm, residual Add, learned
positional embedding — all on (batch, seq, d) tensors with an ('s', 'n')
grid (sequence + sample parallelism).  Capability extensions beyond the
reference (needed for the transformer family; the reference has no
attention models)."""

from __future__ import annotations

from typing import Dict, List

from flexflow_tpu.ops.base import Op, Tensor
from flexflow_tpu.strategy import ParallelConfig


class _SeqElementwise(Op):
    """Shared (s, n)-grid elementwise base: output and preferred input
    layouts are batch-over-n, sequence-over-s, features replicated."""

    AXIS_NAMES = ("s", "n")

    def output_spec(self):
        from jax.sharding import PartitionSpec as P

        return P("n", "s", None)

    def regrid_input_specs(self):
        from jax.sharding import PartitionSpec as P

        return [P("n", "s", None)] * len(self.inputs)


class LayerNormSeq(_SeqElementwise):
    def __init__(self, name: str, pc: ParallelConfig, input: Tensor,
                 eps: float = 1e-5):
        super().__init__(name, pc, [input])
        assert input.ndim == 3
        self.eps = eps
        self.d = input.shape[2]
        self.output = Tensor(input.shape, input.dtype, self, name)

    def init_params(self, rng) -> Dict:
        import jax.numpy as jnp

        return {"scale": jnp.ones((self.d,), "float32"),
                "bias": jnp.zeros((self.d,), "float32")}

    def param_specs(self):
        from jax.sharding import PartitionSpec as P

        return {"scale": P(None), "bias": P(None)}

    def forward(self, params, state, xs: List, train: bool):
        import jax
        import jax.numpy as jnp

        (x,) = xs
        xf = x.astype("float32")
        mu = xf.mean(axis=-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + self.eps)
        y = y * params["scale"] + params["bias"]
        return y.astype(x.dtype), state

    def flops_per_sample(self) -> float:
        return 8.0 * self.output.shape[1] * self.d

    def param_bytes(self) -> int:
        return 8 * self.d


class AddSeq(_SeqElementwise):
    """``x + scale * y``: a residual sum, ``scale`` a published
    ``residual_multiplier`` on the branch (1 leaves it as it is)."""

    def __init__(self, name: str, pc: ParallelConfig, inputs: List[Tensor],
                 scale: float = 1.0):
        super().__init__(name, pc, inputs)
        assert len(inputs) == 2 and inputs[0].shape == inputs[1].shape
        self.scale = float(scale)
        self.output = Tensor(inputs[0].shape, inputs[0].dtype, self, name)

    def forward(self, params, state, xs: List, train: bool):
        if self.scale != 1.0:       # one rounding, of the sum
            x, y = (v.astype("float32") for v in xs)
            return (x + self.scale * y).astype(xs[0].dtype), state
        return xs[0] + xs[1], state

    def flops_per_sample(self) -> float:
        import math

        return float(math.prod(self.output.shape[1:]))


class GeluSeq(_SeqElementwise):
    def __init__(self, name: str, pc: ParallelConfig, input: Tensor):
        super().__init__(name, pc, [input])
        assert input.ndim == 3
        self.output = Tensor(input.shape, input.dtype, self, name)

    def forward(self, params, state, xs: List, train: bool):
        import jax

        return jax.nn.gelu(xs[0]), state

    def flops_per_sample(self) -> float:
        import math

        return 8.0 * float(math.prod(self.output.shape[1:]))


class PosEmbed(_SeqElementwise):
    """Learned positional embedding added to the token embedding."""

    def __init__(self, name: str, pc: ParallelConfig, input: Tensor):
        super().__init__(name, pc, [input])
        assert input.ndim == 3
        self.seq_len = input.shape[1]
        self.d = input.shape[2]
        self.output = Tensor(input.shape, input.dtype, self, name)

    def init_params(self, rng) -> Dict:
        import jax

        return {"table": jax.random.normal(
            rng, (self.seq_len, self.d), "float32") * 0.02}

    def param_specs(self):
        from jax.sharding import PartitionSpec as P

        return {"table": P("s", None)}

    def forward(self, params, state, xs: List, train: bool):
        (x,) = xs
        return x + params["table"].astype(x.dtype), state

    def param_bytes(self) -> int:
        return 4 * self.seq_len * self.d
