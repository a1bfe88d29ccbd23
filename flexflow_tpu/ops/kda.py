"""Kimi Delta Attention (KDA; the Kimi Linear report, arXiv:2510.26692,
and inclusionAI's ``bailing_hybrid`` layers) for training: a gated delta
rule whose state forgets each key channel at a rate of its own.  Three
operators, as ``ops/ssm.py`` splits Mamba-2, so that a trace reads the
scan by name:

    [q | k | v | f | z | b] = x W_in                            KDAIn
    q, k, v <- silu(causal depthwise conv of ``taps`` taps)      (no bias)
    q, k    <- q / |q|, k / |k|              a head at a time, float32
    g_t     = lower_bound * sigmoid(exp(A_log) * (f_t + dt_bias))
    beta_t  = sigmoid(b_t)                                 one a head

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1}      KDAScan
          + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(head_k_dim)

    out = (RMSNorm_head(o) * sigmoid(z)) W_out                  KDAOut

``S`` is a head's ``head_k_dim x head_v_dim`` state, ``g_t`` its key
channels' log-decays, each in ``(lower_bound, 0)``; ``A_log`` is one a
head, ``dt_bias`` one a channel.  The norm of the output runs over each
head's values with one gain the heads share (``group_norm_size`` 1).

The scan runs in chunks of ``chunk`` steps (64).  With ``G`` the
cumulative sum of ``g`` inside a chunk, ``A[t, s] = sum_c k_t[c] k_s[c]
exp(G_t[c] - G_s[c])`` for ``s < t`` and ``M`` the same of ``q_t`` with
``k_s`` for ``s <= t``, the chunk entering with state ``S`` has

    U  = T diag(beta) (V - (k * exp(G)) S),  T = (I + diag(beta) A)^-1
    O  = (q * exp(G)) S + M U
    S' = Diag(exp(G_end)) S + (k * exp(G_end - G))^T U
       = P S + R,   P = Diag(exp(G_end)) - K^T W,   R = K^T U0

(``W``, ``U0``: ``T diag(beta)`` of ``k * exp(G)`` and of ``V``, one
triangular solve, by blocks of a sub-chunk's rows).  ``P`` is a matrix, not a decay, and the chunks'
transitions do not commute: the chunks are walked one after another
(``lax.scan`` over ``S' = P S + R``, float32 at the highest precision),
and everything else a chunk needs is computed for the chunks of a span of
``span`` steps (1024) at once, each span recomputed in the backward pass
(its intermediates for a whole 16 384-step sequence of 32 heads would
take several GB).  The factors of ``A`` and ``M`` keep their exponents
in range: a float32 ``exp`` of a cumulative decay overflows beyond 17
steps at the bound of -5 (-5 x 17.7 = -88.7), so each sub-chunk of
``sub`` (16) rows is factored about the cumulative decay at its middle
row, where both factors stay within ``exp(+-40)`` (about the row before
it they would reach ``exp(+-80)``, and the backward pass's partial sums
of them fall below float32's normal range, which a CPU flushes to zero).
A sequence that the chunk does not divide is padded with steps that
change nothing (``k = v = beta = g = 0``).  The products take the
compute type with float32 accumulation; decays, cumulative sums, the
solve and the carried state are float32.  A traced scan counts
``kernels.kda.xla.<heads>x<head_k_dim>c<chunk>``: plain XLA is the one
form there is.

Grids: ('s', 'n') as the other sequence operators', of which only (1, 1)
is implemented (a split sequence would hand states between shards).
"""

from __future__ import annotations

import math
from typing import Dict, List

from flexflow_tpu import obs
from flexflow_tpu.ops.base import Op, Tensor
from flexflow_tpu.ops.seq_common import _SeqElementwise
from flexflow_tpu.ops.seq_gated import rms_norm
from flexflow_tpu.ops.ssm import causal_conv1d
from flexflow_tpu.strategy import ParallelConfig

#: steps a chunk, rows a sub-chunk factored about one reference, and
#: steps a span whose chunks are computed together and recomputed in the
#: backward pass
CHUNK = 64
SUB = 16
SPAN = 1024


def l2_normalize(x, eps: float = 1e-6):
    """x / sqrt(sum(x^2) + eps) over the last axis, in float32."""
    import jax
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True)
                              + eps)


def unit_lower_solve(n, rhs, block: int):
    """``(I + n)^-1 rhs`` for ``n`` strictly lower triangular (.., l, l)
    and ``rhs`` (.., l, m), float32 at the highest precision, by blocks of
    ``block`` rows: each diagonal block inverted by forward substitution a
    row at a time, then forward substitution over the blocks.  Products
    and row updates only: XLA's triangular solve took 1.4 ms a call at a
    span's 512 systems of 64 on the chip."""
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    nb = n.shape[-1] // block
    rows = lambda a, i: a[..., i * block:(i + 1) * block, :]
    cols = lambda a, j: a[..., j * block:(j + 1) * block]
    d = jnp.stack([cols(rows(n, i), i) for i in range(nb)], axis=-3)
    inverses = jnp.broadcast_to(jnp.eye(block, dtype=n.dtype), d.shape)
    for r in range(1, block):           # every diagonal block at once
        inverses = inverses.at[..., r, :].add(-jnp.einsum(
            "...j,...jk->...k", d[..., r, :], inverses, precision=hi))
    solved = []
    for i in range(nb):
        part = rows(rhs, i)
        for j in range(i):
            part = part - jnp.einsum("...ij,...jm->...im",
                                     cols(rows(n, i), j), solved[j],
                                     precision=hi)
        solved.append(jnp.einsum("...ij,...jm->...im",
                                 inverses[..., i, :, :], part, precision=hi))
    return jnp.concatenate(solved, axis=-2)


def kda_chunked(q, k, v, g, beta, chunk: int = CHUNK, sub: int = SUB,
                span: int = SPAN):
    """The chunked scan.  q, k (B, S, H, K) in the compute type, unit
    rows, q already scaled; v (B, S, H, V) in the compute type; g (B, S,
    H, K) float32 log-decays, none above 0 or below -5 at ``sub`` 16; beta
    (B, S, H) float32 -> o (B, S, H, V) in v's type."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32, cdt = jnp.float32, v.dtype
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    sub = min(int(sub), int(chunk))
    if chunk % sub:
        raise ValueError(f"a chunk of {chunk} is no whole number of "
                         f"sub-chunks of {sub}")
    l = min(int(chunk), -(-s // sub) * sub)
    pad = -s % l
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    nc, n = (s + pad) // l, l // sub
    per = max(c for c in range(1, min(nc, max(1, span // l)) + 1)
              if nc % c == 0)

    def spans(a):           # (B, S, H, ..) -> (spans, B, per, H, l, ..)
        a = a.reshape((b, nc // per, per, l) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 4, 3), 1, 0)

    def mm(spec, x, y):
        return jnp.einsum(spec, x.astype(cdt), y.astype(cdt),
                          preferred_element_type=f32)

    t_idx = jnp.arange(l)
    seen = (t_idx[None, :] < (jnp.arange(n)[:, None] + 1) * sub)[:, :, None]

    def within(qc, kc, vc, gc, bc):
        """What a span's chunks need of one another's state, all chunks
        at once: P, R, W, U0, M and q * exp(G)."""
        cg = jnp.cumsum(gc, axis=-2)                       # (B, c, H, l, K)
        # each sub-chunk's reference: the cumulative decay at its middle
        by_sub = cg.reshape(b, per, h, n, sub, dk)
        ref = by_sub[..., (sub - 1) // 2, :]               # (B, c, H, n, K)
        down = jnp.exp(by_sub - ref[..., None, :])
        up = jnp.exp(jnp.where(seen, ref[..., None, :]
                               - cg[..., None, :, :], -jnp.inf))
        right = kc[..., None, :, :].astype(f32) * up   # (B, c, H, n, l, K)

        def scores(rows):   # rows (B, c, H, l, K) -> (B, c, H, l, l)
            left = rows.reshape(b, per, h, n, sub, dk).astype(f32) * down
            return mm("...nik,...njk->...nij", left, right).reshape(
                b, per, h, l, l)

        a = jnp.where(t_idx[:, None] > t_idx[None, :], scores(kc), 0.0)
        m = jnp.where(t_idx[:, None] >= t_idx[None, :], scores(qc), 0.0)
        decayed = jnp.exp(cg)
        x = unit_lower_solve(bc[..., :, None] * a, bc[..., None] * (
            jnp.concatenate([kc.astype(f32) * decayed, vc.astype(f32)],
                            axis=-1)), sub)
        w, u0 = x[..., :dk], x[..., dk:]
        end = cg[..., -1, :]                               # (B, c, H, K)
        k_end = kc.astype(f32) * jnp.exp(end[..., None, :] - cg)
        p = jnp.exp(end)[..., :, None] * jnp.eye(dk, dtype=f32) \
            - mm("...ck,...cj->...kj", k_end, w)           # (B, c, H, K, K)
        r = mm("...ck,...cv->...kv", k_end, u0)            # (B, c, H, K, V)
        return p, r, w, u0, m, qc.astype(f32) * decayed

    def walk(state, pr):
        pc, rc = pr
        return jnp.einsum("bhij,bhjv->bhiv", pc, state,
                          precision=lax.Precision.HIGHEST) + rc, state

    @jax.checkpoint
    def walk_span(state, xs):
        p, r, w, u0, m, q_dec = within(*xs)
        state, entering = lax.scan(
            walk, state, (jnp.moveaxis(p, 1, 0), jnp.moveaxis(r, 1, 0)))
        entering = jnp.moveaxis(entering, 0, 1)            # (B, c, H, K, V)
        u = u0 - mm("...ck,...kv->...cv", w, entering)
        return state, mm("...ck,...kv->...cv", q_dec, entering) \
            + mm("...ts,...sv->...tv", m, u)               # (B, c, H, l, V)

    _, o = lax.scan(walk_span, jnp.zeros((b, h, dk, dv), f32), (
        spans(q), spans(k), spans(v), spans(g.astype(f32)),
        spans(beta.astype(f32))))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 4).reshape(b, nc * l, h, dv)
    return o[:, :s].astype(cdt)


def _only_whole_grid(op: Op):
    if any(p != 1 for p in op.pc.dims):
        raise ValueError(
            f"op {op.name!r}: the KDA operators run on the grid (1, 1) "
            f"only; {op.pc.dims} (sequence or batch parts) is not "
            f"implemented")


class KDAIn(_SeqElementwise):
    """One product in and its split, the causal convolutions of q, k and
    v with their SiLU, the unit rows of q and k, the log-decays and the
    write strengths.  Outputs ``q``, ``k``, ``v`` and the output gate's
    logits ``z`` (B, S, heads * head_dim) in the input's type, ``g`` (B,
    S, heads * head_dim) and ``beta`` (B, S, heads) in float32."""

    def __init__(self, name: str, pc: ParallelConfig, input: Tensor,
                 num_heads: int, head_dim: int, taps: int,
                 lower_bound: float):
        super().__init__(name, pc, [input])
        assert input.ndim == 3
        n, s, d = input.shape
        self.d_model = d
        self.num_heads, self.head_dim = int(num_heads), int(head_dim)
        self.taps = int(taps)
        self.lower_bound = float(lower_bound)
        self.d_inner = self.num_heads * self.head_dim
        inner = (n, s, self.d_inner)
        self.q, self.k, self.v, self.z = (
            Tensor(inner, input.dtype, self, f"{name}:{w}") for w in "qkvz")
        self.g = Tensor(inner, "float32", self, name + ":g")
        self.beta = Tensor((n, s, self.num_heads), "float32", self,
                           name + ":beta")
        self.output = self.q
        self.outputs = [self.q, self.k, self.v, self.g, self.beta, self.z]

    @property
    def d_proj(self) -> int:
        return 5 * self.d_inner + self.num_heads

    def init_params(self, rng) -> Dict:
        """``w_in`` glorot uniform; the convolutions as ``nn.Conv1d``
        starts them; ``A_log`` the log of a rate uniform in 1-16 a head
        and ``dt_bias`` the inverse softplus of steps log-uniform in
        0.001-0.1 a channel (the published KDA layer's starts)."""
        import jax
        import jax.numpy as jnp

        k_in, k_conv, k_a, k_dt = jax.random.split(rng, 4)
        bound = 1.0 / math.sqrt(self.taps)
        dt = jnp.exp(jax.random.uniform(k_dt, (self.d_inner,), "float32")
                     * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        return {"w_in": jax.nn.initializers.glorot_uniform()(
                    k_in, (self.d_model, self.d_proj), "float32"),
                "conv_w": jax.random.uniform(
                    k_conv, (3 * self.d_inner, self.taps), "float32",
                    -bound, bound),
                "A_log": jnp.log(jax.random.uniform(
                    k_a, (self.num_heads,), "float32", 1.0, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt))}

    def param_specs(self):
        from jax.sharding import PartitionSpec as P

        return {"w_in": P(None, None), "conv_w": P(None, None),
                "A_log": P(None), "dt_bias": P(None)}

    def output_specs(self):
        return [self.output_spec()] * 6

    def validate_partitioning(self):
        super().validate_partitioning()
        _only_whole_grid(self)

    def forward(self, params, state, xs: List, train: bool):
        import jax
        import jax.numpy as jnp

        (u,) = xs
        b, s, _ = u.shape
        di, h, hd = self.d_inner, self.num_heads, self.head_dim
        proj = jnp.einsum("bsd,de->bse", u, params["w_in"].astype(u.dtype),
                          preferred_element_type=jnp.float32).astype(u.dtype)
        qkv = jax.nn.silu(causal_conv1d(proj[..., :3 * di],
                                        params["conv_w"], None))
        q, k = (l2_normalize(qkv[..., i * di:(i + 1) * di].reshape(
            b, s, h, hd)).reshape(b, s, di).astype(u.dtype) for i in (0, 1))
        v = qkv[..., 2 * di:].astype(u.dtype)
        rate = jnp.repeat(jnp.exp(params["A_log"].astype(jnp.float32)), hd)
        g = self.lower_bound * jax.nn.sigmoid(
            rate * (proj[..., 3 * di:4 * di].astype(jnp.float32)
                    + params["dt_bias"]))
        z = proj[..., 4 * di:5 * di]
        beta = jax.nn.sigmoid(proj[..., 5 * di:].astype(jnp.float32))
        return (q, k, v, g, beta, z), state

    def cost_signature(self) -> tuple:
        return (self.num_heads, self.head_dim, self.taps, self.lower_bound)

    def flops_per_sample(self) -> float:
        s = self.q.shape[1]
        return s * (2.0 * self.d_model * self.d_proj
                    + 2.0 * self.taps * 3 * self.d_inner)

    def param_bytes(self) -> int:
        return 4 * (self.d_model * self.d_proj + 3 * self.d_inner * self.taps
                    + self.num_heads + self.d_inner)


class KDAScan(_SeqElementwise):
    """Everything between ``q, k, v, g, beta`` and ``o``: the delta rule
    in its chunked form (:func:`kda_chunked`), q scaled by
    ``head_dim ** -0.5``.  No parameters."""

    def __init__(self, name: str, pc: ParallelConfig, q: Tensor, k: Tensor,
                 v: Tensor, g: Tensor, beta: Tensor, num_heads: int,
                 head_dim: int, lower_bound: float):
        super().__init__(name, pc, [q, k, v, g, beta])
        self.num_heads, self.head_dim = int(num_heads), int(head_dim)
        self.lower_bound = float(lower_bound)
        n, s, width = q.shape
        assert width == self.num_heads * self.head_dim
        assert beta.shape == (n, s, self.num_heads)
        #: steps a chunk: the model's, or a shorter sequence in whole
        #: sub-chunks
        self.chunk = min(CHUNK, -(-s // SUB) * SUB)
        self.output = Tensor(q.shape, q.dtype, self, name)

    def validate_partitioning(self):
        super().validate_partitioning()
        _only_whole_grid(self)

    def forward(self, params, state, xs: List, train: bool):
        q, k, v, g, beta = xs
        b, s, _ = q.shape
        h, hd, chunk = self.num_heads, self.head_dim, self.chunk
        obs.count(f"kernels.kda.xla.{h}x{hd}c{chunk}")
        obs.count("kda.chunk", chunk, level=True)
        obs.count("kda.chunks_per_sequence", -(-s // chunk), level=True)
        obs.count("kda.gate_lower_bound", self.lower_bound, level=True)
        heads = lambda a: a.reshape(b, s, h, -1)
        o = kda_chunked(heads(q * hd ** -0.5), heads(k), heads(v), heads(g),
                        beta, chunk)
        return o.reshape(b, s, h * hd), state

    def cost_signature(self) -> tuple:
        return (self.num_heads, self.head_dim, self.chunk)

    def flops_per_sample(self) -> float:
        """The chunked form's products a token (``benchmarks/flops/
        ling_3_0_flash.py`` counts them the same way)."""
        s, l, hd = self.output.shape[1], self.chunk, self.head_dim
        return s * self.num_heads * (8.0 * l * hd + 8.0 * hd * hd
                                     + 2.0 * hd ** 3 / l)

    def param_bytes(self) -> int:
        return 0


class KDAOut(_SeqElementwise):
    """``(RMSNorm_head(o) * sigmoid(z)) W_out``: each head's values
    normed with one gain the heads share, then the output gate, then the
    output projection."""

    def __init__(self, name: str, pc: ParallelConfig, o: Tensor, z: Tensor,
                 head_dim: int, d_model: int, eps: float = 1e-6):
        super().__init__(name, pc, [o, z])
        assert o.shape == z.shape and o.ndim == 3
        self.d_inner, self.head_dim = o.shape[2], int(head_dim)
        self.d_model = int(d_model)
        self.eps = float(eps)
        self.output = Tensor(o.shape[:2] + (self.d_model,), o.dtype, self,
                             name)

    def init_params(self, rng) -> Dict:
        import jax
        import jax.numpy as jnp

        return {"norm": jnp.ones((self.head_dim,), "float32"),
                "w_out": jax.nn.initializers.glorot_uniform()(
                    rng, (self.d_inner, self.d_model), "float32")}

    def param_specs(self):
        from jax.sharding import PartitionSpec as P

        return {"norm": P(None), "w_out": P(None, None)}

    def validate_partitioning(self):
        super().validate_partitioning()
        _only_whole_grid(self)

    def forward(self, params, state, xs: List, train: bool):
        import jax
        import jax.numpy as jnp

        o, z = xs
        b, s, _ = o.shape
        normed = rms_norm(o.reshape(b, s, -1, self.head_dim),
                          params["norm"], self.eps).reshape(b, s, -1)
        gated = (normed.astype(jnp.float32)
                 * jax.nn.sigmoid(z.astype(jnp.float32))).astype(o.dtype)
        out = jnp.einsum("bse,ed->bsd", gated,
                         params["w_out"].astype(o.dtype),
                         preferred_element_type=jnp.float32)
        return out.astype(o.dtype), state

    def flops_per_sample(self) -> float:
        s = self.output.shape[1]
        return s * (2.0 * self.d_inner * self.d_model + 8.0 * self.d_inner)

    def param_bytes(self) -> int:
        return 4 * (self.head_dim + self.d_inner * self.d_model)
