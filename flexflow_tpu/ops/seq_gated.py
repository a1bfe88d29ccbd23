"""What a transformer block since 2023 is made of beside attention:
RMSNorm, rotary positions and the gated SiLU feed-forward, on
``(batch, seq, d)`` tensors.  The norm follows the ('s', 'n') grid of the
other sequence elementwise ops; the feed-forward takes the ('c', 'n') grid
of the sequence linear, 'c' splitting its hidden width.  No bias anywhere,
as the published blocks have none.
"""

from __future__ import annotations

from typing import Dict, List

from flexflow_tpu.ops.base import Op, Tensor
from flexflow_tpu.ops.seq_common import _SeqElementwise
from flexflow_tpu.strategy import ParallelConfig

#: how the rotary dimensions pair up.  ``split``: the published
#: deepseek_v3 code, which de-interleaves (x0, x2, .. | x1, x3, ..) and
#: rotates the halves against each other; ``adjacent``: (x0, x1), (x2, x3)
#: rotated in place.  They differ by one fixed permutation of the rotated
#: vector, applied to queries and keys alike, so every score is the same.
ROPE_PAIRINGS = ("split", "adjacent")


def rms_norm(x, scale, eps: float):
    """x / sqrt(mean(x^2) + eps) * scale over the last axis, in float32,
    returned in x's type."""
    import jax
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def rope_angles(seq_length: int, dim: int, theta: float):
    """cos and sin, each (seq, dim/2) float32: position p turns pair i by
    ``p * theta ** (-2 i / dim)``."""
    import jax.numpy as jnp

    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(seq_length, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin, pairing: str = "split"):
    """Rotate the last axis of ``x`` (.., seq, [heads,] dim).  ``cos`` and
    ``sin`` are (seq, dim/2); a heads axis between seq and dim is
    broadcast over."""
    import jax.numpy as jnp

    if pairing not in ROPE_PAIRINGS:
        raise ValueError(f"rope pairing {pairing!r}: one of {ROPE_PAIRINGS}")
    half = x.shape[-1] // 2
    if x.ndim == 4:                       # (B, S, H, dim)
        cos, sin = cos[:, None, :], sin[:, None, :]
    xf = x.astype(jnp.float32)
    pairs = xf.reshape(*x.shape[:-1], half, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    ra, rb = a * cos - b * sin, a * sin + b * cos
    if pairing == "split":
        out = jnp.concatenate([ra, rb], axis=-1)
    else:
        out = jnp.stack([ra, rb], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


class RMSNormSeq(_SeqElementwise):
    def __init__(self, name: str, pc: ParallelConfig, input: Tensor,
                 eps: float = 1e-5):
        super().__init__(name, pc, [input])
        assert input.ndim == 3
        self.eps = eps
        self.d = input.shape[2]
        self.output = Tensor(input.shape, input.dtype, self, name)

    def init_params(self, rng) -> Dict:
        import jax.numpy as jnp

        return {"scale": jnp.ones((self.d,), "float32")}

    def param_specs(self):
        from jax.sharding import PartitionSpec as P

        return {"scale": P(None)}

    def forward(self, params, state, xs: List, train: bool):
        return rms_norm(xs[0], params["scale"], self.eps), state

    def flops_per_sample(self) -> float:
        return 4.0 * self.output.shape[1] * self.d

    def param_bytes(self) -> int:
        return 4 * self.d


def gated_ffn(x, w_gate, w_up, w_down):
    """``(silu(x w_gate) * (x w_up)) w_down`` on (.., d): products in x's
    type with float32 accumulation, the gate in float32."""
    import jax
    import jax.numpy as jnp

    def mm(a, w):
        return jnp.einsum("...d,df->...f", a, w.astype(a.dtype),
                          preferred_element_type=jnp.float32)

    h = (jax.nn.silu(mm(x, w_gate)) * mm(x, w_up)).astype(x.dtype)
    return mm(h, w_down).astype(x.dtype)


class GatedFFNSeq(Op):
    """Gated SiLU feed-forward of hidden width ``d_ff``; the shared
    experts of an expert layer are one of these at their summed width."""

    AXIS_NAMES = ("c", "n")

    def __init__(self, name: str, pc: ParallelConfig, input: Tensor,
                 d_ff: int):
        super().__init__(name, pc, [input])
        assert input.ndim == 3
        self.d = input.shape[2]
        self.d_ff = int(d_ff)
        self.output = Tensor(input.shape, input.dtype, self, name)

    def init_params(self, rng) -> Dict:
        import jax

        keys = jax.random.split(rng, 3)
        init = jax.nn.initializers.glorot_uniform()
        return {"w_gate": init(keys[0], (self.d, self.d_ff), "float32"),
                "w_up": init(keys[1], (self.d, self.d_ff), "float32"),
                "w_down": init(keys[2], (self.d_ff, self.d), "float32")}

    def param_specs(self):
        from jax.sharding import PartitionSpec as P

        return {"w_gate": P(None, "c"), "w_up": P(None, "c"),
                "w_down": P("c", None)}

    def output_spec(self):
        from jax.sharding import PartitionSpec as P

        return P("n", None, None)

    def regrid_input_specs(self):
        from jax.sharding import PartitionSpec as P

        return [P("n", None, None)]

    def forward(self, params, state, xs: List, train: bool):
        return gated_ffn(xs[0], params["w_gate"], params["w_up"],
                         params["w_down"]), state

    def validate_partitioning(self):
        super().validate_partitioning()
        if self.d_ff % self.pc.dims[0]:
            raise ValueError(
                f"op {self.name!r}: hidden width {self.d_ff} not divisible "
                f"by its 'c' parts {self.pc.dims[0]} (grid {self.pc.dims})")

    def flops_per_sample(self) -> float:
        return 6.0 * self.output.shape[1] * self.d * self.d_ff

    def param_bytes(self) -> int:
        return 4 * 3 * self.d * self.d_ff
