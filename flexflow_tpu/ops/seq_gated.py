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


def rotary_table(rule: Dict, seq_length: int):
    """cos and sin, each (seq, rotated/2) float32, of one rotary rule: a
    dict with ``dim`` (the leading dimensions of a head that turn; the
    rest pass through), ``rope_theta`` and ``rope_type``:

    * ``default``: :func:`rope_angles`;
    * ``yarn`` (arXiv:2309.00071, as ``transformers``'
      ``_compute_yarn_parameters`` computes it): pair ``i`` turns by the
      blend ``(1 - r_i) / (factor theta^(2i/dim)) + r_i / theta^(2i/dim)``,
      ``r_i`` 1 below the dimension that makes ``beta_fast`` turns over
      ``original_max_position_embeddings`` positions, 0 above the one
      that makes ``beta_slow``, linear between (both rounded outwards);
      cos and sin are multiplied by ``attention_factor`` (``0.1 ln(factor)
      + 1`` where the rule gives none), at every length."""
    import math

    import jax.numpy as jnp

    dim, theta = int(rule["dim"]), float(rule["rope_theta"])
    kind = rule.get("rope_type", "default")
    if kind == "default":
        return rope_angles(seq_length, dim, theta)
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}: 'default' or 'yarn'")
    factor = float(rule["factor"])
    original = float(rule["original_max_position_embeddings"])

    def turns_at(turns):    # the dimension that makes this many turns
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_at(float(rule.get("beta_fast", 32)))), 0)
    high = min(math.ceil(turns_at(float(rule.get("beta_slow", 1)))),
               dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * i / dim)
    keep = 1.0 - jnp.clip((i - low) / (high - low), 0.0, 1.0)
    inv = plain / factor * (1.0 - keep) + plain * keep
    ang = jnp.arange(seq_length, dtype=jnp.float32)[:, None] * inv[None, :]
    grow = float(rule.get("attention_factor")
                 or 0.1 * math.log(factor) + 1.0)
    return grow * jnp.cos(ang), grow * jnp.sin(ang)


def apply_rope(x, cos, sin, pairing: str = "split"):
    """Rotate the last axis of ``x`` (.., seq, [heads,] dim).  ``cos`` and
    ``sin`` are (seq, dim/2); a heads axis between seq and dim is
    broadcast over.  Tables narrower than ``dim/2`` turn the leading
    ``2 x`` their width of the axis and the rest passes through.

    The statement of the mathematics, and the path of every caller but
    one: ``LatentAttention`` on every backend, ``GroupedQueryAttention``
    off the TPU and wherever ``ops/pallas/rope.fits`` refuses its head.
    On the TPU a head of whole lane tiles is turned by ``ff_rope``
    (``ops/pallas/rope.py``), which the tests hold to this function: on
    the 4-D view XLA pays for the de-interleave below with relayouts of
    the whole array (PERF.md section 6, PR 37)."""
    import jax.numpy as jnp

    if pairing not in ROPE_PAIRINGS:
        raise ValueError(f"rope pairing {pairing!r}: one of {ROPE_PAIRINGS}")
    turned = 2 * cos.shape[-1]
    if turned < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :turned], cos, sin, pairing),
             x[..., turned:]], axis=-1)
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


def rope_by_products(x, cos, sin, heads: int):
    """:func:`apply_rope` (the ``split`` pairing) on the row-major ``(B, S,
    heads * head_dim)`` array without a 4-D view: the de-interleave and
    the swap of the halves are two products with constant matrices of 0,
    1 and -1 (``x P`` = ``(x0, x2, .. | x1, x3, .. | the rest)``, ``x Q`` =
    ``(-x1, -x3, .. | x0, x2, .. | 0)``, block-diagonal over the heads:
    exact, one term a column, float32 out), then ``x P * C + x Q * S`` with
    ``C`` = cos on the turned lanes and 1 on the rest, ``S`` = sin and 0,
    rounded once.  The same numbers as ``apply_rope`` to the last bit.

    For the TPU at a head ``ops/pallas/rope.fits`` refuses: there XLA's
    code for ``apply_rope``'s stride-2 lane slices on a ``(B, S, heads,
    64)`` view did not finish a step of the LFM2 cell in ten minutes
    (PERF.md section 6, PR 38), and two plain products of the width
    squared (1.5 ms a pass at 32 heads of 64) cost the idle MXU little."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    b, s, width = x.shape
    d, half = width // heads, cos.shape[-1]
    turned = 2 * half
    p, q = np.zeros((d, d), np.float32), np.zeros((d, d), np.float32)
    i = np.arange(half)
    p[2 * i, i] = p[2 * i + 1, half + i] = 1.0
    p[np.arange(turned, d), np.arange(turned, d)] = 1.0
    q[2 * i + 1, i], q[2 * i, half + i] = -1.0, 1.0
    eye = jnp.eye(heads, dtype=x.dtype)

    def over_heads(m):      # (d, d) -> block-diagonal (width, width)
        m = jnp.asarray(m, x.dtype)
        return (eye[:, None, :, None] * m[None, :, None, :]).reshape(
            width, width)

    f32 = jnp.float32
    exact = jax.lax.Precision.HIGHEST if x.dtype == f32 else None
    xp, xq = (jnp.einsum("bsw,wv->bsv", x, over_heads(m), precision=exact,
                         preferred_element_type=f32) for m in (p, q))
    rest = ((0, 0), (0, d - turned))
    c = jnp.tile(jnp.pad(jnp.concatenate([cos, cos], -1), rest,
                         constant_values=1.0), (1, heads))
    sn = jnp.tile(jnp.pad(jnp.concatenate([sin, sin], -1), rest),
                  (1, heads))
    return (xp * c + xq * sn).astype(x.dtype)


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
