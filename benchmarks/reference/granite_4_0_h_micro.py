"""granite-4.0-h-micro (ibm-granite; ``model_type`` ``granitemoehybrid``),
or the leading layers of it that one chip holds, in plain ``jax.numpy``
and float32.  Pre-norm blocks, RMSNorm, no bias but the convolution's:

    h = 12 E[ids];   h <- h + 0.22 Mixer(RMSNorm(h));
    h <- h + 0.22 FFN(RMSNorm(h));   logits = RMSNorm(h) E^T / 8

(``embedding_multiplier``, ``residual_multiplier``, ``logits_scaling``;
the embedding is tied).  ``FFN(v) = Wdown(silu(v Wgate) * (v Wup))``.  The
mixer is chosen by ``layer_types``:

``mamba`` (Mamba-2): ``[z | xBC | dt] = u Win``; ``xBC_t[c] <- silu(b[c]
+ sum_j w[c, j] xBC_{t-3+j}[c])`` with zeros before the sequence starts;
``x`` (heads x head width), ``B``, ``C`` (state width, shared by all heads)
split from ``xBC``; ``delta_t = softplus(dt_t + dt_bias)``, ``A =
-exp(A_log)``; then, **one time step after another**,

    H_t[h] = exp(delta_t[h] A[h]) H_{t-1}[h] + delta_t[h] x_t[h] (outer) B_t
    y_t[h] = H_t[h] C_t + D[h] x_t[h]                      H_{-1} = 0

and ``out = RMSNorm(y * silu(z)) Wout``, the gate before the norm and the
norm over the whole inner width.  This is the recurrence itself and not
the chunked form the system runs.

``attention``: ``q = u Wq`` (heads x 64), ``k, v = u Wk, u Wv`` (key-value
heads x 64, query heads ``g j .. g j + g - 1`` reading key-value head
``j``), ``softmax(attention_multiplier q k^T + causal mask) v``, no
rotary, no bias, ``Wo``.

The loss is the summed next-token cross-entropy.

Departures from the published code, none of which changes a number
beyond rounding: the published mixer runs a fused chunked kernel where
this walks the steps; so that it fits beside the float32 weights at
published widths every block, every 64 steps of the recurrence, every
128 query rows of the attention and every 512 rows of the head are
recomputed in the backward pass (``jax.checkpoint`` changes no
arithmetic), a block takes its sequences one after another, and the
(tokens, vocabulary) logits never stand whole.  The
routed experts the family can have are absent here as they are in the
published configuration (``num_local_experts`` 0).  What the
configuration file assumes (plain SGD, initial values) lies outside this
file.
"""

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
ROW_BLOCK = 512          # rows of the head a block
QUERY_BLOCK = 128        # query rows of the attention a block
STEP_BLOCK = 64          # steps of the recurrence a block
# What a run in a coarser format would read (tools/chip_granite_probe.py
# sets it, nothing else does): the type every product's operands are
# rounded to first.  None: float32 as it stands.
OPERANDS = None


def _round(a):
    if OPERANDS is None:
        return a
    return a.astype(OPERANDS).astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(_round(a), _round(b), precision=HI)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _block_of(n, most):
    """The largest block of at most ``most`` that divides n."""
    return max(r for r in range(1, min(n, most) + 1) if n % r == 0)


def _glu(n, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(n, w_gate)) * _mm(n, w_up), w_down)


def _causal_conv(x, w, b):
    """x (S, C), w (C, K), b (C,): tap j of channel c meets x_{t-K+1+j}."""
    s, k = x.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return b + sum(w[:, j] * padded[j:j + s] for j in range(k))


def _recurrence(x, delta, a, b, c):
    """One sequence, one step after another: x (S, H, P), delta (S, H),
    a (H,), b and c (S, N) -> (S, H, P)."""
    s, h, p = x.shape
    n = b.shape[-1]
    steps = _block_of(s, STEP_BLOCK)

    def step(state, inputs):
        x_t, d_t, b_t, c_t = inputs
        state = jnp.exp(d_t * a)[:, None, None] * state \
            + _round(d_t[:, None] * x_t)[:, :, None] * _round(b_t)
        return state, jnp.sum(_round(state) * _round(c_t), axis=-1)

    @jax.checkpoint
    def block(state, inputs):
        return lax.scan(step, state, inputs)

    split = lambda v: v.reshape((s // steps, steps) + v.shape[1:])
    _, y = lax.scan(block, jnp.zeros((h, p, n), jnp.float32),
                    (split(x), split(delta), split(b), split(c)))
    return y.reshape(s, h, p)


def _mamba(p_in, p_scan, p_out, u, c):
    """One sequence u (S, hidden) through the Mamba-2 mixer."""
    s = u.shape[0]
    h, hd = int(c["mamba_n_heads"]), int(c["mamba_d_head"])
    n = int(c["mamba_d_state"])
    inner = h * hd
    proj = _mm(u, p_in["w_in"])
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * n],
                  proj[:, 2 * inner + 2 * n:])
    bias = p_in["conv_b"] if c.get("mamba_conv_bias", True) else 0.0
    xbc = jax.nn.silu(_causal_conv(xbc, p_in["conv_w"], bias))
    x = xbc[:, :inner].reshape(s, h, hd)
    delta = jax.nn.softplus(dt + p_in["dt_bias"])
    y = _recurrence(x, delta, -jnp.exp(p_scan["A_log"]),
                    xbc[:, inner:inner + n], xbc[:, inner + n:])
    y = (y + p_scan["D"][:, None] * x).reshape(s, inner)
    gated = _rms(y * jax.nn.silu(z), p_out["norm"], float(c["rms_norm_eps"]))
    return _mm(gated, p_out["w_out"])


def _attention(p, u, c):
    """One sequence u (S, hidden): masked softmax, the queries in blocks
    of rows, each recomputed in the backward pass."""
    s = u.shape[0]
    h, kv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    hd = int(c["hidden_size"]) // h
    scale = float(c["attention_multiplier"])
    q = _mm(u, p["wq"]).reshape(s, kv, h // kv, hd)
    k = _mm(u, p["wk"]).reshape(s, kv, hd)
    v = _mm(u, p["wv"]).reshape(s, kv, hd)
    rows = _block_of(s, QUERY_BLOCK)

    @jax.checkpoint
    def block(args):
        qb, first = args
        scores = jnp.einsum("qjgd,kjd->jgqk", _round(qb), _round(k),
                            precision=HI) * scale
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(s)[None, :]
        prob = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("jgqk,kjd->qjgd", _round(prob), _round(v),
                          precision=HI)

    out = lax.map(block, (q.reshape(s // rows, rows, kv, h // kv, hd),
                          jnp.arange(s // rows) * rows))
    return _mm(out.reshape(s, h * hd), p["wo"])


def _block(p, i, kind, x, c):
    """Every sequence of x (batch, positions, hidden) through block i,
    one sequence after another.  ``p`` holds the block's ops.  The mixer
    and the feed-forward are recomputed each by itself, so that the
    backward pass holds the intermediates of one of them, for one
    sequence, at a time."""
    eps, res = float(c["rms_norm_eps"]), float(c["residual_multiplier"])

    @jax.checkpoint
    def mixer(row):
        n = _rms(row, p[f"blk{i}_norm1"]["scale"], eps)
        if kind == "mamba":
            return _mamba(p[f"blk{i}_ssm_in"], p[f"blk{i}_ssm_scan"],
                          p[f"blk{i}_ssm_out"], n, c)
        if kind == "attention":
            return _attention(p[f"blk{i}_attn"], n, c)
        raise ValueError(f"layer type {kind!r}")

    @jax.checkpoint
    def feed_forward(row):
        return _glu(_rms(row, p[f"blk{i}_norm2"]["scale"], eps),
                    **p[f"blk{i}_ffn"])

    x = x + res * lax.map(mixer, x)
    return x + res * lax.map(feed_forward, x)


def hidden(params, tokens, config):
    """(batch, positions, hidden) after the final norm."""
    x = float(config["embedding_multiplier"]) \
        * params["embed"]["table"][tokens]
    for i, kind in enumerate(config["layer_types"]):
        mine = {k: v for k, v in params.items() if k.startswith(f"blk{i}_")}
        x = jax.checkpoint(
            lambda p, x, i=i, kind=kind: _block(p, i, kind, x, config))(
                mine, x)
    return _rms(x, params["final_norm"]["scale"],
                float(config["rms_norm_eps"]))


def _nll_sum(table, x, targets, counted, scaling):
    """Summed cross-entropy of the ``counted`` rows of x (N, d) against
    targets (N,) under the tied head, the rows in blocks so that the
    (N, vocabulary) logits never stand whole."""
    n = x.shape[0]
    rows = _block_of(n, ROW_BLOCK)

    @jax.checkpoint
    def block(args):
        xb, tb, cb = args
        logits = jnp.einsum("nd,vd->nv", _round(xb), _round(table),
                            precision=HI) / scaling
        lp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(cb * jnp.take_along_axis(lp, tb[:, None],
                                                 axis=-1)[:, 0])

    split = lambda a: a.reshape((n // rows, rows) + a.shape[1:])
    return jnp.sum(lax.map(block, (split(x), split(targets),
                                   split(counted))))


def sum_loss_and_grads(params, batch, config):
    """Position i predicts token i+1; the last position has no target."""
    tokens, labels = batch
    b, s = tokens.shape

    targets = jnp.concatenate([labels[:, 1:], labels[:, :1]], axis=1)
    counted = jnp.broadcast_to(jnp.arange(s) < s - 1, (b, s))

    def f(p):
        x = hidden(p, tokens, config)
        return _nll_sum(p["embed"]["table"], x.reshape(b * s, -1),
                        targets.reshape(-1),
                        counted.reshape(-1).astype(jnp.float32),
                        float(config["logits_scaling"]))

    loss, grads = jax.value_and_grad(f)(params)
    return loss, grads, b * (s - 1)
