"""The Mamba-2 mixer (Dao & Gu 2024, "Transformers are SSMs"; the layer
``granitemoehybrid`` and ``mamba2`` configurations name ``mamba``), as
three operators so that a trace reads the scan by name:

    [z | xBC | dt] = u W_in                                      SSMIn
    xBC_t[c] <- silu(b[c] + sum_j w[c, j] xBC_{t-K+1+j}[c])      (zeros
                                            before the sequence starts)
    delta_t  = softplus(dt_t + dt_bias)              float32, one a head

    H_t[h] = exp(delta_t[h] A[h]) H_{t-1}[h]                     SSMScan
             + delta_t[h] x_t[h] (outer) B_t          A = -exp(A_log)
    y_t[h] = H_t[h] C_t + D[h] x_t[h]

    out = RMSNorm(y * silu(z)) W_out                             SSMOut

``x`` is ``heads x head_dim`` of ``xBC``, ``B`` and ``C`` are ``d_state``
wide and shared by every head (one group, ``mamba_n_groups`` 1; more is
refused).  The gate comes before the norm, and the norm runs over the
whole inner width (one group).

The scan runs in the chunked ("state-space dual") form: inside a chunk
of ``chunk`` steps ``Y = (L * (C B^T)) (delta x)`` with ``L[t, s] =
exp(sum_{s<r<=t} delta_r A)`` for ``s <= t``; a chunk's own state ``sum_s
exp(sum_{s<r<=end} delta_r A) delta_s x_s (outer) B_s``; the recurrence
over a sequence's chunks; and what the entering state adds, ``exp(cumsum)
C_t H_in``.  Decays, cumulative sums, softplus and the carried state are
float32; the products take the compute type with float32 accumulation.  A
sequence that the chunk does not divide is padded with steps of ``delta =
0``, which leave the state as it is and add nothing.

Two forms of it, one algorithm.  ``xla_chunked`` (:func:`ssd_chunked`) is
plain ``jax.numpy``: every backend runs it, the tests hold the kernels to
it, and it writes a chunk's ``(heads, chunk, chunk)`` decay matrix and
``m`` to HBM (the recurrence over chunks as one small product with the
decays between chunks, no loop in the program).  ``pallas``
(``ops/pallas/ssd_scan.py``) makes the same matrices in VMEM a (sequence,
chunk, group of heads) at a time, carries the state from chunk to chunk
in VMEM, and has a backward of its own.  Which runs is decided where the
operator is traced, from what the code observes and by no switch: the one
kernel gate (``ops/pallas.flash_enabled()``: the backend is a TPU) and the
shapes the kernels hold (``ssd_scan.fits``: a chunk of 128 or 256,
heads in groups of eight that are 16, 32 or 64 wide, up to 4096 columns
in all, a state of 128, bfloat16 or float32; the Granite cell's shape is
the one timed on the chip); every other shape, the
tests' chunks of 1, 3, 10 and 64 among them, keeps ``xla_chunked``.
Which form ran is counted once a trace in
``kernels.ssd.<form>.<chunk>x<heads>x<state>``.

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
from flexflow_tpu.strategy import ParallelConfig


def causal_conv1d(x, w, b):
    """Depthwise causal convolution along a sequence: x (B, S, C), w
    (C, K), b (C,) or None -> ``y_t[c] = b[c] + sum_j w[c, j]
    x_{t-K+1+j}[c]`` with zeros before the sequence starts; float32,
    whatever x's type.  (The gated short convolution of
    ``ops/short_conv.py`` is the caller without a bias.)"""
    import jax.numpy as jnp

    k = w.shape[1]
    s = x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    y = None if b is None else b.astype(jnp.float32)
    for j in range(k):
        tap = w[:, j].astype(jnp.float32) * xp[:, j:j + s]
        y = tap if y is None else y + tap
    return y


def ssd_chunked(x, dt, a, b, c, d, chunk: int):
    """The chunked scan.  x (B, S, H, P) in the compute type, dt (B, S, H)
    float32 time steps (after softplus), a (H,) float32 and negative,
    b and c (B, S, N) in the compute type, d (H,) float32 -> y as x."""
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    l = max(1, min(int(chunk), s))
    pad = -s % l
    if pad:
        x, dt, b, c = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b, c))
    nc = (s + pad) // l
    cdt = x.dtype
    xc = x.reshape(bsz, nc, l, h, p).astype(f32)
    dtc = dt.astype(f32).reshape(bsz, nc, l, h)
    bc, cc = b.reshape(bsz, nc, l, n), c.reshape(bsz, nc, l, n)
    # cs[t] = sum_{r<=t} delta_r A inside the chunk, a head a row
    cs = jnp.cumsum(dtc * a, axis=2)                       # (B, c, l, H)
    cs_h = cs.transpose(0, 1, 3, 2)                        # (B, c, H, l)

    # inside a chunk: Y = (L * (C B^T)) (delta x)
    cb = jnp.einsum("bctn,bcsn->bcts", cc, bc, preferred_element_type=f32)
    seen = jnp.tril(jnp.ones((l, l), bool))
    decay = jnp.exp(jnp.where(
        seen, cs_h[..., :, None] - cs_h[..., None, :], -jnp.inf))
    m = (cb[:, :, None] * decay).astype(cdt)               # (B, c, H, t, s)
    dtx = (xc * dtc[..., None]).astype(cdt)                # (B, c, l, H, P)
    y = jnp.einsum("bchts,bcshp->bcthp", m, dtx, preferred_element_type=f32)

    # a chunk's own state, decayed to the chunk's end
    to_end = jnp.exp(cs[:, :, -1:, :] - cs)
    states = jnp.einsum("bclhp,bcln->bchpn",
                        (xc * (dtc * to_end)[..., None]).astype(cdt), bc,
                        preferred_element_type=f32)        # (B, c, H, P, N)
    # the recurrence over chunks as one product: chunk i enters with
    # sum_{j<i} exp(sum_{j<k<i} total_k) states_j, in float32 throughout
    total = cs[:, :, -1, :]                                # (B, c, H)
    upto = jnp.cumsum(total, axis=1)
    before = jnp.tril(jnp.ones((nc, nc), bool), -1)
    carry = jnp.exp(jnp.where(
        before[None, :, :, None],
        (upto - total)[:, :, None, :] - upto[:, None, :, :], -jnp.inf))
    entering = jnp.einsum("bijh,bjhpn->bihpn", carry, states,
                          precision=lax.Precision.HIGHEST)

    # what the entering state adds: exp(cs_t) C_t H_in
    y = y + jnp.exp(cs)[..., None] * jnp.einsum(
        "bcln,bchpn->bclhp", cc, entering.astype(cdt),
        preferred_element_type=f32)
    y = y + d[:, None] * xc
    return y.reshape(bsz, s + pad, h, p)[:, :s].astype(cdt)


def _only_whole_grid(op: Op):
    if any(p != 1 for p in op.pc.dims):
        raise ValueError(
            f"op {op.name!r}: the state-space operators run on the grid "
            f"(1, 1) only; {op.pc.dims} (sequence or batch parts) is not "
            f"implemented")


class SSMIn(_SeqElementwise):
    """Input projection and split, the causal depthwise convolution with
    its SiLU, and the softplus time steps.  Outputs ``z`` (B, S, H*P) and
    ``xBC`` (B, S, H*P + 2N) in the input's type and ``delta`` (B, S, H)
    in float32."""

    def __init__(self, name: str, pc: ParallelConfig, input: Tensor,
                 num_heads: int, head_dim: int, d_state: int, d_conv: int,
                 conv_bias: bool = True):
        super().__init__(name, pc, [input])
        assert input.ndim == 3
        n, s, d = input.shape
        self.d_model = d
        self.num_heads, self.head_dim = int(num_heads), int(head_dim)
        self.d_state, self.d_conv = int(d_state), int(d_conv)
        self.conv_bias = bool(conv_bias)
        self.d_inner = self.num_heads * self.head_dim
        self.d_xbc = self.d_inner + 2 * self.d_state
        self.z = Tensor((n, s, self.d_inner), input.dtype, self, name + ":z")
        self.xbc = Tensor((n, s, self.d_xbc), input.dtype, self,
                          name + ":xBC")
        self.delta = Tensor((n, s, self.num_heads), "float32", self,
                            name + ":delta")
        self.output = self.z
        self.outputs = [self.z, self.xbc, self.delta]

    @property
    def d_proj(self) -> int:
        return self.d_inner + self.d_xbc + self.num_heads

    def init_params(self, rng) -> Dict:
        """``w_in`` glorot uniform; the convolution as ``nn.Conv1d``
        starts it (uniform within 1/sqrt(K), a channel its own fan-in);
        ``dt_bias`` the inverse softplus of time steps log-uniform in
        0.001-0.1 (the Mamba-2 convention)."""
        import jax
        import jax.numpy as jnp

        k_in, k_conv, k_bias, k_dt = jax.random.split(rng, 4)
        bound = 1.0 / math.sqrt(self.d_conv)
        dt = jnp.exp(jax.random.uniform(k_dt, (self.num_heads,), "float32")
                     * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        p = {"w_in": jax.nn.initializers.glorot_uniform()(
                 k_in, (self.d_model, self.d_proj), "float32"),
             "conv_w": jax.random.uniform(
                 k_conv, (self.d_xbc, self.d_conv), "float32", -bound, bound),
             "dt_bias": dt + jnp.log(-jnp.expm1(-dt))}
        if self.conv_bias:
            p["conv_b"] = jax.random.uniform(
                k_bias, (self.d_xbc,), "float32", -bound, bound)
        return p

    def param_specs(self):
        from jax.sharding import PartitionSpec as P

        specs = {"w_in": P(None, None), "conv_w": P(None, None),
                 "dt_bias": P(None)}
        if self.conv_bias:
            specs["conv_b"] = P(None)
        return specs

    def output_specs(self):
        return [self.output_spec()] * 3

    def validate_partitioning(self):
        super().validate_partitioning()
        _only_whole_grid(self)

    def forward(self, params, state, xs: List, train: bool):
        import jax
        import jax.numpy as jnp

        (u,) = xs
        proj = jnp.einsum("bsd,de->bse", u, params["w_in"].astype(u.dtype),
                          preferred_element_type=jnp.float32).astype(u.dtype)
        di, dx = self.d_inner, self.d_xbc
        z, xbc, dt = proj[..., :di], proj[..., di:di + dx], proj[..., di + dx:]
        bias = params["conv_b"] if self.conv_bias \
            else jnp.zeros((dx,), jnp.float32)
        xbc = jax.nn.silu(causal_conv1d(xbc, params["conv_w"], bias))
        delta = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
        return (z, xbc.astype(u.dtype), delta), state

    def cost_signature(self) -> tuple:
        return (self.num_heads, self.head_dim, self.d_state, self.d_conv)

    def flops_per_sample(self) -> float:
        s = self.z.shape[1]
        return s * (2.0 * self.d_model * self.d_proj
                    + 2.0 * self.d_conv * self.d_xbc)

    def param_bytes(self) -> int:
        return 4 * (self.d_model * self.d_proj + self.d_xbc * self.d_conv
                    + self.conv_bias * self.d_xbc + self.num_heads)


class SSMScan(_SeqElementwise):
    """Everything between ``x, B, C, delta`` and ``y``: the state-space
    scan in its chunked form and the skip ``D x``."""

    def __init__(self, name: str, pc: ParallelConfig, xbc: Tensor,
                 delta: Tensor, num_heads: int, head_dim: int, d_state: int,
                 chunk: int):
        super().__init__(name, pc, [xbc, delta])
        self.num_heads, self.head_dim = int(num_heads), int(head_dim)
        self.d_state = int(d_state)
        self.d_inner = self.num_heads * self.head_dim
        n, s, width = xbc.shape
        #: steps a chunk: the model's, or the whole of a shorter sequence
        self.chunk = max(1, min(int(chunk), s))
        assert width == self.d_inner + 2 * self.d_state
        assert delta.shape == (n, s, self.num_heads)
        self.output = Tensor((n, s, self.d_inner), xbc.dtype, self, name)

    def init_params(self, rng) -> Dict:
        """``A = -exp(A_log)`` starts at -1 .. -heads, ``D`` at 1."""
        import jax.numpy as jnp

        return {"A_log": jnp.log(jnp.arange(1, self.num_heads + 1,
                                            dtype=jnp.float32)),
                "D": jnp.ones((self.num_heads,), "float32")}

    def param_specs(self):
        from jax.sharding import PartitionSpec as P

        return {"A_log": P(None), "D": P(None)}

    def validate_partitioning(self):
        super().validate_partitioning()
        _only_whole_grid(self)

    def forward(self, params, state, xs: List, train: bool):
        import jax.numpy as jnp

        from flexflow_tpu.ops import pallas
        from flexflow_tpu.ops.pallas import ssd_scan

        xbc, delta = xs
        b, s, _ = xbc.shape
        di, n, chunk = self.d_inner, self.d_state, self.chunk
        a = -jnp.exp(params["A_log"].astype(jnp.float32))
        d = params["D"].astype(jnp.float32)
        # the kernels where the backend is a TPU and the shapes are theirs
        kernels = pallas.flash_enabled() and ssd_scan.fits(
            chunk, self.num_heads, self.head_dim, n, xbc.dtype)
        form = "pallas" if kernels else "xla_chunked"
        obs.count(f"kernels.ssd.{form}.{chunk}x{self.num_heads}x{n}")
        obs.count("ssm.chunk", chunk, level=True)
        obs.count("ssm.chunks_per_sequence", -(-s // chunk), level=True)
        if kernels:
            return ssd_scan.ssd_scan(
                xbc, delta, a, d, heads=self.num_heads,
                head_dim=self.head_dim, state=n, chunk=chunk), state
        y = ssd_chunked(
            xbc[..., :di].reshape(b, s, self.num_heads, self.head_dim),
            delta, a, xbc[..., di:di + n], xbc[..., di + n:], d, chunk)
        return y.reshape(b, s, di), state

    def cost_signature(self) -> tuple:
        return (self.num_heads, self.head_dim, self.d_state, self.chunk)

    def flops_per_sample(self) -> float:
        """The chunked form's four products a token: C B^T once for all
        heads, and a head's (L * C B^T) (delta x), own state and entering
        state."""
        s, l = self.output.shape[1], self.chunk
        h, p, n = self.num_heads, self.head_dim, self.d_state
        return s * (2.0 * l * n + h * (2.0 * l * p + 4.0 * p * n))

    def param_bytes(self) -> int:
        return 4 * 2 * self.num_heads


class SSMOut(_SeqElementwise):
    """``RMSNorm(y * silu(z)) W_out``: the gate first, then the norm over
    the whole inner width with a gain, then the output projection."""

    def __init__(self, name: str, pc: ParallelConfig, y: Tensor, z: Tensor,
                 d_model: int, eps: float = 1e-5):
        super().__init__(name, pc, [y, z])
        assert y.shape == z.shape and y.ndim == 3
        self.d_inner = y.shape[2]
        self.d_model = int(d_model)
        self.eps = float(eps)
        self.output = Tensor(y.shape[:2] + (self.d_model,), y.dtype, self,
                             name)

    def init_params(self, rng) -> Dict:
        import jax
        import jax.numpy as jnp

        return {"norm": jnp.ones((self.d_inner,), "float32"),
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

        y, z = xs
        gated = (y.astype(jnp.float32)
                 * jax.nn.silu(z.astype(jnp.float32))).astype(y.dtype)
        g = rms_norm(gated, params["norm"], self.eps)
        out = jnp.einsum("bse,ed->bsd", g, params["w_out"].astype(g.dtype),
                         preferred_element_type=jnp.float32)
        return out.astype(y.dtype), state

    def flops_per_sample(self) -> float:
        s = self.output.shape[1]
        return s * (2.0 * self.d_inner * self.d_model + 6.0 * self.d_inner)

    def param_bytes(self) -> int:
        return 4 * (self.d_inner + self.d_inner * self.d_model)
