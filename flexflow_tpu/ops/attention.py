"""Multi-head attention with a full SOAP grid: ('s', 'h', 'n') = sequence
(context parallelism) x heads (tensor parallelism) x batch (data
parallelism).

Execution paths:
  * s-parts == 1 on TPU: the hand-written Pallas flash kernel
    (ops/pallas/flash_attention.py) — scores stay in VMEM, blocks stream
    through the MXU; multi-device grids run it per-shard under shard_map
    (head/batch sharding is embarrassingly parallel).
  * s-parts == 1 elsewhere (or shapes the kernel can't shard): blockwise
    (flash-style streaming-softmax) attention in plain XLA; head/batch
    sharding handled by GSPMD from the specs.
  * s-parts > 1 on a canonical full-device grid: explicit ring attention
    (shard_map + ppermute over the 's' mesh axis, see
    parallel/ring_attention.py) — K/V blocks rotate on neighbor links, O(S/P)
    memory per chip.


:class:`GroupedQueryAttention` is the causal layer of the 2023-on decoder
blocks: more query heads than key-value heads, no bias, a softmax scale
the model gives and, each where the model asks for it, RMSNorms on each
head of q and k, rotary positions on q and k, a sliding window and a
per-head gate on the result.

New capability relative to the reference (which has no attention ops,
SURVEY.md §2.6); cited rows: CP/ring-attention, SP."""

from __future__ import annotations

from typing import Dict, List

# loaded with the operator (1.3 s of jax.experimental.pallas), not inside
# the first trace; looked up through the module so a test can patch the gate
from flexflow_tpu.ops import pallas
from flexflow_tpu.ops.base import Op, Tensor
from flexflow_tpu.strategy import ParallelConfig


class MultiHeadAttention(Op):
    AXIS_NAMES = ("s", "h", "n")

    def __init__(self, name: str, pc: ParallelConfig, input: Tensor,
                 num_heads: int, causal: bool = False, machine=None):
        super().__init__(name, pc, [input])
        assert input.ndim == 3
        n, s, d = input.shape
        assert d % num_heads == 0, "d_model must divide into heads"
        self.num_heads = num_heads
        self.head_dim = d // num_heads
        self.d_model = d
        self.causal = causal
        self.machine = machine  # needed for the explicit ring-attention mesh
        self.output = Tensor(input.shape, input.dtype, self, name)

    def init_params(self, rng) -> Dict:
        import jax
        import jax.numpy as jnp

        d = self.d_model
        keys = jax.random.split(rng, 4)
        init = jax.nn.initializers.glorot_uniform()
        return {
            "wq": init(keys[0], (d, d), "float32"),
            "wk": init(keys[1], (d, d), "float32"),
            "wv": init(keys[2], (d, d), "float32"),
            "wo": init(keys[3], (d, d), "float32"),
            "bo": jnp.zeros((d,), "float32"),
        }

    def param_specs(self):
        from jax.sharding import PartitionSpec as P

        # q/k/v projections column-sharded by heads, output row-sharded
        return {"wq": P(None, "h"), "wk": P(None, "h"), "wv": P(None, "h"),
                "wo": P("h", None), "bo": P(None)}

    def output_spec(self):
        from jax.sharding import PartitionSpec as P

        return P("n", "s", None)

    def regrid_input_specs(self):
        from jax.sharding import PartitionSpec as P

        # batch over n, sequence over s, d replicated over h (the q/k/v
        # projections are column-sharded by head)
        return [P("n", "s", None)]

    def _use_ring(self) -> bool:
        s_parts = self.pc.dims[0]
        return (s_parts > 1 and self.machine is not None
                and self.machine.is_canonical(self.pc))

    def forward(self, params, state, xs: List, train: bool):
        import jax.numpy as jnp

        from flexflow_tpu.parallel.ring_attention import ring_attention

        (x,) = xs

        def proj(w):  # (B, S, H*hd): head i in columns i*hd:(i+1)*hd
            return jnp.einsum("bsd,de->bse", x, w.astype(x.dtype),
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)

        q, k, v = proj(params["wq"]), proj(params["wk"]), proj(params["wv"])
        if self._use_ring():
            mesh = self.machine.mesh_for(self.pc, self.AXIS_NAMES)
            out = self._merge_heads(ring_attention(
                *map(self._split_heads, (q, k, v)), mesh, "s", self.causal))
        else:
            out = self._flash_or_blockwise(q, k, v)
        out = out.astype(x.dtype)
        if (self.machine is not None and self.machine.num_devices > 1
                and self.pc.dims[1] > 1):
            # head TP: keep the merged activation head-sharded along d so
            # the wo projection is row-parallel (contraction dim sharded,
            # GSPMD psums partial products — the Megatron pair to the
            # column-parallel q/k/v).  Without this the activation arrives
            # batch-sharded and the wo weight-grad dot forces a
            # full-rematerialization reshard in the backward pass.
            from jax import lax
            from jax.sharding import PartitionSpec as P

            out = lax.with_sharding_constraint(
                out, self.machine.sharding(self.pc, self.AXIS_NAMES,
                                           P("n", "s", "h")))
        y = jnp.einsum("bsd,de->bse", out, params["wo"].astype(x.dtype),
                       preferred_element_type=jnp.float32).astype(x.dtype)
        return y + params["bo"].astype(x.dtype), state

    def _flash_or_blockwise(self, q, k, v):
        """Non-ring attention body on the projections' (B, S, H*hd) layout:
        the Pallas flash kernel on TPU, which reads that layout as it is
        (direct on one device; per-shard under shard_map on a canonical
        multi-device grid, where head/batch sharding is embarrassingly
        parallel), otherwise the XLA streaming-softmax path with GSPMD
        sharding."""
        from flexflow_tpu.ops.pallas.flash_attention import \
            flash_attention_packed
        from flexflow_tpu.parallel.ring_attention import blockwise_attention

        b, s, _ = q.shape
        h = self.num_heads
        if pallas.flash_enabled():
            nd = self.machine.num_devices if self.machine is not None else 1
            if nd == 1 or len(self.pc.devices) == 1:
                return flash_attention_packed(q, k, v, h, self.causal)
            _, ph, pn = self.pc.dims
            if (self.machine.is_canonical(self.pc)
                    and b % max(pn, 1) == 0 and h % max(ph, 1) == 0):
                from jax.sharding import PartitionSpec as P

                from flexflow_tpu.parallel.ring_attention import \
                    unchecked_shard_map

                mesh = self.machine.mesh_for(self.pc, self.AXIS_NAMES)
                spec = P("n" if pn > 1 else None, None,
                         "h" if ph > 1 else None)
                return unchecked_shard_map(
                    lambda ql, kl, vl: flash_attention_packed(
                        ql, kl, vl, h // max(ph, 1), self.causal),
                    mesh, (spec, spec, spec), spec)(q, k, v)

        return self._merge_heads(blockwise_attention(
            *map(self._split_heads, (q, k, v)), self.causal,
            block_size=min(s, 512)))

    def _split_heads(self, y):
        """(B, S, H*hd) -> (B, H, S, hd)."""
        b, s, _ = y.shape
        return y.reshape(b, s, self.num_heads, -1).transpose(0, 2, 1, 3)

    @staticmethod
    def _merge_heads(y):
        """(B, H, S, hd) -> (B, S, H*hd)."""
        b, h, s, hd = y.shape
        return y.transpose(0, 2, 1, 3).reshape(b, s, h * hd)

    def local_clone(self, pc: ParallelConfig):
        ps, ph, pn = pc.dims
        n, s, d = self.inputs[0].shape
        if ps > 1 or ph > 1:
            # A standalone shard-shaped clone cannot represent ring-CP
            # ((S/ps) x S scores against full-length K/V) or head-TP
            # (d x d/ph projections) — it would under-measure by ps / ph.
            # Fall back to the analytic roofline, whose flops/num_parts
            # division IS exact for these grids (total work is preserved).
            return None
        if n % pn:
            return None
        t = Tensor((n // pn, s, d))
        return MultiHeadAttention(self.name, ParallelConfig((1, 1, 1), (0,)),
                                  t, self.num_heads, self.causal)

    def flops_per_sample(self) -> float:
        s, d = self.output.shape[1], self.d_model
        return 8.0 * s * d * d + 4.0 * s * s * d

    def param_bytes(self) -> int:
        return 4 * (4 * self.d_model * self.d_model + self.d_model)


def grouped_causal_attention(q, k, v, num_heads: int, num_kv_heads: int,
                             scale: float, window: int = None):
    """softmax(scale q k^T, causal) v in plain XLA, query heads
    ``g j .. g j + g - 1`` reading key-value head ``j``: q (B, S, H*d), k
    and v (B, S, KV*d) -> (B, S, H*d); under ``window`` a query sees
    itself and the ``window - 1`` keys before it.  The path off the TPU
    (CPU tests, tiny sizes): the score matrix is whole, and no key is
    repeated."""
    import jax
    import jax.numpy as jnp

    b, s, _ = q.shape
    g = num_heads // num_kv_heads
    qh = q.reshape(b, s, num_kv_heads, g, -1)
    kh, vh = (x.reshape(b, s, num_kv_heads, -1) for x in (k, v))
    scores = jnp.einsum("bqjgd,bkjd->bjgqk", qh, kh,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.tril(jnp.ones((s, s), bool))
    if window is not None:
        mask = mask & ~jnp.tril(jnp.ones((s, s), bool), -int(window))
    p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bjgqk,bkjd->bqjgd", p.astype(v.dtype), vh,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, -1).astype(q.dtype)


class GroupedQueryAttention(Op):
    """Causal attention with ``num_heads`` query heads on ``num_kv_heads``
    key-value heads (query heads ``g j .. g j + g - 1`` read key-value head
    ``j``), ``softmax(scale q k^T + causal mask) v`` with ``scale`` a
    given number and no bias.  Four things a model may add, and without
    them the operator is what it was, parameter for parameter
    (``granitemoehybrid`` takes none of them, ``laguna`` the rotary rule,
    the window and the gate, ``lfm2_moe`` the norms and the rotary rule):

    * ``qk_norm``: the eps of an RMSNorm over the ``head_dim`` values of
      each head of q and of k, with one learned gain vector for q
      (``q_norm``) and one for k (``k_norm``) shared by their heads,
      before the rotary turn; float32 statistics, rounded to the
      operands' type where the rotary reads them (``attn.qk_norm`` counts
      a traced layer);
    * ``rope``: a rotary rule (``ops/seq_gated.rotary_table``: the
      dimensions of a head that turn, theta, default or YaRN) applied to q
      and k here, where alone they exist.  On the TPU, for heads of whole
      lane tiles (``ops/pallas/rope.fits``), by ``ff_rope`` on the
      ``(B, S, heads * head_dim)`` arrays as the products write them and
      the flash kernels read them; the de-interleave of ``apply_rope``'s
      pairs lives inside that kernel, as a product with a 0/1 matrix, so
      ``wq`` and ``wk`` are multiplied, updated and compared in their
      published column order whichever path runs
      (``kernels.rope.pallas.<heads>x<head_dim>r<turned>`` or
      ``kernels.rope.xla.<..>`` counts a traced call).  On the TPU at a
      head the kernel refuses (64: half a lane tile) the same rotation
      runs as two products with 0/1 matrices in that layout
      (``ops/seq_gated.rope_by_products``), not on a 4-D view;
    * ``window``: a query sees itself and the ``window - 1`` keys before
      it (the flash kernels skip the tiles left of the window as they skip
      those above the diagonal; ``attn.window`` reads it);
    * ``gate``: one more matrix ``wg`` (hidden x heads) and ``sigmoid(x
      wg)``, one number a head and token, on that head's result before
      ``wo`` (arXiv:2505.06708's headwise gate).

    On the TPU the scores run in the flash kernels, which take one key a
    query head: k and v are repeated to ``num_heads`` heads in HBM first
    (``attn.kv_groups`` reads the copies a key gets and
    ``attn.kv_groups.<g>`` counts the traced layers of each group size;
    PERF.md section 3 has the bytes).  Grid ('s', 'h', 'n') as the
    attention operator's, of which only (1, 1, 1) is implemented."""

    AXIS_NAMES = ("s", "h", "n")

    def __init__(self, name: str, pc: ParallelConfig, input: Tensor,
                 num_heads: int, num_kv_heads: int, head_dim: int,
                 scale: float, rope: Dict = None, window: int = None,
                 gate: bool = False, qk_norm: float = None):
        super().__init__(name, pc, [input])
        assert input.ndim == 3
        if num_heads % num_kv_heads:
            raise ValueError(f"op {name!r}: {num_heads} query heads do not "
                             f"divide over {num_kv_heads} key-value heads")
        self.d_model = input.shape[2]
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.scale = float(scale)
        self.rope = dict(rope) if rope else None
        if self.rope and not 0 < int(self.rope["dim"]) <= self.head_dim:
            raise ValueError(f"op {name!r}: {self.rope['dim']} rotary "
                             f"dimensions in a head of {self.head_dim}")
        self.window = None if window is None else int(window)
        self.gate = bool(gate)
        self.qk_norm = None if qk_norm is None else float(qk_norm)
        self.output = Tensor(input.shape, input.dtype, self, name)

    def _shapes(self) -> Dict:
        d, hd = self.d_model, self.head_dim
        shapes = {"wq": (d, self.num_heads * hd),
                  "wk": (d, self.num_kv_heads * hd),
                  "wv": (d, self.num_kv_heads * hd),
                  "wo": (self.num_heads * hd, d)}
        if self.gate:
            shapes["wg"] = (d, self.num_heads)
        return shapes

    def init_params(self, rng) -> Dict:
        import jax

        shapes = self._shapes()
        keys = jax.random.split(rng, len(shapes))
        init = jax.nn.initializers.glorot_uniform()
        params = {k: init(key, shape, "float32")
                  for key, (k, shape) in zip(keys, shapes.items())}
        for k in self._gains():
            params[k] = jax.numpy.ones((self.head_dim,), "float32")
        return params

    def _gains(self) -> tuple:
        """The names of the head norms' gain vectors, each (head_dim,)."""
        return ("q_norm", "k_norm") if self.qk_norm is not None else ()

    def param_specs(self):
        from jax.sharding import PartitionSpec as P

        specs = {k: P(None, None) for k in self._shapes()}
        specs.update({k: P(None) for k in self._gains()})
        return specs

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
                f"op {self.name!r}: grouped-query attention runs on the "
                f"grid (1, 1, 1) only; {self.pc.dims} (sequence, head or "
                f"batch parts) is not implemented")

    def forward(self, params, state, xs: List, train: bool):
        import jax
        import jax.numpy as jnp

        from flexflow_tpu import obs
        from flexflow_tpu.ops.pallas.flash_attention import \
            flash_attention_packed

        (x,) = xs
        b, s, _ = x.shape
        h, kv, hd = self.num_heads, self.num_kv_heads, self.head_dim

        def proj(a, w):
            return jnp.einsum("bsd,de->bse", a, w.astype(a.dtype),
                              preferred_element_type=jnp.float32
                              ).astype(a.dtype)

        q, k, v = (proj(x, params[w]) for w in ("wq", "wk", "wv"))
        if self.qk_norm is not None:
            from flexflow_tpu.ops.seq_gated import rms_norm

            obs.count("attn.qk_norm")
            q, k = (rms_norm(y.reshape(b, s, heads, hd), params[gain],
                             self.qk_norm).reshape(b, s, heads * hd)
                    for y, heads, gain in ((q, h, "q_norm"),
                                           (k, kv, "k_norm")))
        if self.rope:
            q, k = self._turned(q, k)
        # the level is what a one-group model's cell reads; the count by
        # group size tells a model's layers apart
        obs.count("attn.kv_groups", h // kv, level=True)
        obs.count(f"attn.kv_groups.{h // kv}")
        if self.window is not None:
            obs.count("attn.window", self.window, level=True)
        if pallas.flash_enabled():
            def repeat(a):      # (B, S, KV*hd) -> (B, S, H*hd)
                a = a.reshape(b, s, kv, 1, hd)
                return jnp.broadcast_to(
                    a, (b, s, kv, h // kv, hd)).reshape(b, s, h * hd)

            out = flash_attention_packed(q, repeat(k), repeat(v), h,
                                         causal=True, scale=self.scale,
                                         window=self.window)
        else:
            out = grouped_causal_attention(q, k, v, h, kv, self.scale,
                                           self.window)
        out = out.astype(x.dtype)
        if self.gate:
            g = jax.nn.sigmoid(jnp.einsum(
                "bsd,dh->bsh", x, params["wg"].astype(x.dtype),
                preferred_element_type=jnp.float32))
            out = (out.reshape(b, s, h, hd) * g[..., None]
                   ).astype(x.dtype).reshape(b, s, h * hd)
        return proj(out, params["wo"]), state

    def _turned(self, q, k):
        """q and k (B, S, heads * head_dim) with their rotary positions.
        Where ``rope.fits``, ``ff_rope`` turns them in the layout they
        are made and read in, one pass each; on a TPU at a head it
        refuses, ``rope_by_products`` in the same layout; else
        ``apply_rope`` on the 4-D view, as on every other backend."""
        from flexflow_tpu import obs
        from flexflow_tpu.ops.pallas import rope
        from flexflow_tpu.ops.seq_gated import (apply_rope, rope_by_products,
                                                rotary_table)

        b, s, _ = q.shape
        hd, rotated = self.head_dim, int(self.rope["dim"])
        cos, sin = rotary_table(self.rope, s)
        kernel = rope.fits(hd, rotated, q.dtype)
        turned = []
        for y, heads in ((q, self.num_heads), (k, self.num_kv_heads)):
            obs.count(f"kernels.rope.{'pallas' if kernel else 'xla'}."
                      f"{heads}x{hd}r{rotated}")
            if kernel:
                turned.append(rope.rope_packed(y, cos, sin, heads))
            elif pallas.flash_enabled():
                # a TPU and no kernel for this head: no 4-D view there
                turned.append(rope_by_products(y, cos, sin, heads))
            else:
                turned.append(apply_rope(y.reshape(b, s, heads, hd), cos,
                                         sin).reshape(b, s, heads * hd))
        return turned

    def cost_signature(self) -> tuple:
        sig = (self.num_heads, self.num_kv_heads, self.head_dim, self.scale)
        if self.rope or self.window is not None or self.gate:
            sig += (tuple(sorted(self.rope.items())) if self.rope else None,
                    self.window, self.gate)
        if self.qk_norm is not None:
            sig += (("qk_norm", self.qk_norm),)
        return sig

    def flops_per_sample(self) -> float:
        s = self.output.shape[1]
        proj = sum(2.0 * a * b_ for a, b_ in self._shapes().values())
        # a query at position i meets i + 1 keys, at most the window's
        met = (s + 1) / 2
        if self.window is not None and self.window < s:
            w = self.window
            met = (w * (w + 1) / 2 + (s - w) * w) / s
        attn = 4.0 * self.num_heads * self.head_dim * met
        norms = 4.0 * self.head_dim * (self.num_heads + self.num_kv_heads) \
            if self.qk_norm is not None else 0.0
        return s * (proj + attn + norms)

    def param_bytes(self) -> int:
        return 4 * (sum(a * b_ for a, b_ in self._shapes().values())
                    + len(self._gains()) * self.head_dim)
