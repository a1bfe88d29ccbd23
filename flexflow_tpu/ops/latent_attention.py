"""Multi-head latent attention (DeepSeek-V2/V3's MLA) for training.

The keys and values of all heads come from one compressed vector a token
(``kv_rank`` wide, with a norm of its own) plus one rotary key that every
head shares; a head's query and key are ``nope + rope`` wide (192) while
its value is ``v_dim`` wide (128):

    q            = x Wq                      heads x [q_nope | q_pe]
    [c | k_pe]   = x Wkva                    kv_rank + rope
    [k_nope | v] = RMSNorm(c) Wkvb           heads x (nope + v_dim)
    k            = [k_nope | RoPE(k_pe)]     k_pe broadcast over heads
    out          = softmax(RoPE'd q k^T / sqrt(nope + rope), causal) v
    y            = out Wo

No bias, no query compression (``q_lora_rank`` null).  Training computes
the keys and values in full; the absorbed form that serves from the
compressed cache is not built (ROADMAP queue 2).  On the TPU the scores
run in the flash kernels at query/key width 192 and value width 128
(``kernels.flash.pad256v128.fused`` at 8192 positions); elsewhere in
plain XLA.  Grid ('s', 'h', 'n') as the attention operator's, of which
only (1, 1, 1) is implemented and anything else is refused.
"""

from __future__ import annotations

import math
from typing import Dict, List

from flexflow_tpu.ops import pallas     # with the operator: ops/attention.py
from flexflow_tpu.ops.base import Op, Tensor
from flexflow_tpu.ops.seq_gated import apply_rope, rms_norm, rope_angles
from flexflow_tpu.strategy import ParallelConfig


def causal_attention(q, k, v, num_heads: int):
    """softmax(q k^T / sqrt(d), causal) v in plain XLA on the packed
    layout: q, k (B, S, H*d), v (B, S, H*dv) -> (B, S, H*dv).  The path
    off the TPU (CPU tests, tiny sizes): the score matrix is whole."""
    import jax
    import jax.numpy as jnp

    b, s, _ = q.shape
    split = lambda x: x.reshape(b, s, num_heads, -1)
    qh, kh, vh = split(q), split(k), split(v)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qh, kh,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(qh.shape[-1])
    mask = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), vh,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, -1).astype(q.dtype)


class LatentAttention(Op):
    AXIS_NAMES = ("s", "h", "n")

    def __init__(self, name: str, pc: ParallelConfig, input: Tensor,
                 num_heads: int, kv_rank: int, nope_dim: int, rope_dim: int,
                 v_dim: int, rope_theta: float, eps: float = 1e-5):
        super().__init__(name, pc, [input])
        assert input.ndim == 3
        self.d_model = input.shape[2]
        self.num_heads = int(num_heads)
        self.kv_rank = int(kv_rank)
        self.nope_dim, self.rope_dim = int(nope_dim), int(rope_dim)
        self.v_dim = int(v_dim)
        self.rope_theta = float(rope_theta)
        self.eps = float(eps)
        self.output = Tensor(input.shape, input.dtype, self, name)

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    def _shapes(self) -> Dict:
        d, h = self.d_model, self.num_heads
        return {"wq": (d, h * self.qk_dim),
                "wkva": (d, self.kv_rank + self.rope_dim),
                "wkvb": (self.kv_rank, h * (self.nope_dim + self.v_dim)),
                "wo": (h * self.v_dim, d)}

    def init_params(self, rng) -> Dict:
        import jax
        import jax.numpy as jnp

        shapes = self._shapes()
        keys = jax.random.split(rng, len(shapes))
        init = jax.nn.initializers.glorot_uniform()
        p = {k: init(key, shape, "float32")
             for key, (k, shape) in zip(keys, shapes.items())}
        p["kv_norm"] = jnp.ones((self.kv_rank,), "float32")
        return p

    def param_specs(self):
        from jax.sharding import PartitionSpec as P

        return {"wq": P(None, None), "wkva": P(None, None),
                "wkvb": P(None, None), "wo": P(None, None),
                "kv_norm": P(None)}

    def output_spec(self):
        from jax.sharding import PartitionSpec as P

        return P("n", "s", None)

    def regrid_input_specs(self):
        from jax.sharding import PartitionSpec as P

        return [P("n", "s", None)]

    def validate_partitioning(self):
        super().validate_partitioning()
        if any(p != 1 for p in self.pc.dims):
            raise ValueError(
                f"op {self.name!r}: latent attention runs on the grid "
                f"(1, 1, 1) only; {self.pc.dims} (sequence, head or batch "
                f"parts) is not implemented")

    def forward(self, params, state, xs: List, train: bool):
        import jax.numpy as jnp

        from flexflow_tpu import obs
        from flexflow_tpu.ops.pallas.flash_attention import \
            flash_attention_packed

        (x,) = xs
        b, s, _ = x.shape
        h, nope, rope, vd = (self.num_heads, self.nope_dim, self.rope_dim,
                             self.v_dim)

        def proj(a, w):
            return jnp.einsum("bsd,de->bse", a, w.astype(a.dtype),
                              preferred_element_type=jnp.float32
                              ).astype(a.dtype)

        q = proj(x, params["wq"]).reshape(b, s, h, nope + rope)
        ckv = proj(x, params["wkva"])
        c = rms_norm(ckv[..., :self.kv_rank], params["kv_norm"], self.eps)
        kv = proj(c, params["wkvb"]).reshape(b, s, h, nope + vd)
        cos, sin = rope_angles(s, rope, self.rope_theta)
        # the turned dimensions lie behind ``nope`` of a ``nope + rope``
        # wide head, on no lane tile: apply_rope (``pallas/rope.fits``)
        obs.count(f"kernels.rope.xla.{h}x{nope + rope}r{rope}")
        obs.count(f"kernels.rope.xla.1x{rope}r{rope}")
        q_pe = apply_rope(q[..., nope:], cos, sin)
        k_pe = apply_rope(ckv[..., self.kv_rank:], cos, sin)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_pe[:, :, None, :], (b, s, h, rope))],
            axis=-1)
        q, k = q.reshape(b, s, -1), k.reshape(b, s, -1)
        v = kv[..., nope:].reshape(b, s, h * vd)
        if pallas.flash_enabled():
            out = flash_attention_packed(q, k, v, h, causal=True)
        else:
            out = causal_attention(q, k, v, h)
        return proj(out.astype(x.dtype), params["wo"]), state

    def cost_signature(self) -> tuple:
        return (self.num_heads, self.kv_rank, self.nope_dim, self.rope_dim,
                self.v_dim)

    def flops_per_sample(self) -> float:
        s = self.output.shape[1]
        proj = sum(2.0 * a * b_ for a, b_ in self._shapes().values())
        # a query at position i meets i + 1 keys
        attn = 2.0 * self.num_heads * (self.qk_dim + self.v_dim) * (s + 1) / 2
        return s * (proj + attn)

    def param_bytes(self) -> int:
        return 4 * (sum(a * b_ for a, b_ in self._shapes().values())
                    + self.kv_rank)
