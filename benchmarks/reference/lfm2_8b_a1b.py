"""LFM2-8B-A1B (LiquidAI; ``model_type`` ``lfm2_moe``), or the share of it
that one chip holds, in plain ``jax.numpy`` and float32.  Pre-norm blocks,
RMSNorm (``norm_eps`` 1e-5), no bias anywhere, a tied head:

    h = x + Op_l(RMSNorm(x));   y = h + FF_l(RMSNorm(h))

``Op_l`` of a ``conv`` layer, on one sequence ``n`` (positions, hidden):
``[B | C | X] = n Win`` (hidden -> 3 x hidden, thirds in that order);
``u = B * X``; ``v_t = sum_{j=0..K-1} w[:, j] * u_{t-(K-1)+j}`` with ``K =
conv_L_cache`` taps, a channel by itself, ``u`` zero before the sequence
starts, no bias and no activation; ``out = (C * v) Wout``.  Written here as
``K`` shifted copies of ``u``, one position after another being what a
shift is.

``Op_l`` of a ``full_attention`` layer: ``q = n Wq`` (``num_attention_heads``
heads of ``hidden / heads``), ``k = n Wk``, ``v = n Wv``
(``num_key_value_heads`` heads); each head of q and of k through an RMSNorm
over its own values with a learned gain (one gain vector for q, one for
k); rotary positions on q and k, frequencies ``rope_theta^(-2i/dim)`` over
the whole head; scores ``q k^T / sqrt(head_dim)``, query head ``j`` reading
key-value head ``j // (heads / kv heads)``, key ``t`` seen by query ``i``
when ``t <= i``; ``Wo``.  No gate, no window.

``FF_l`` for ``l < num_dense_layers``: ``Wdown(silu(n Wgate) * (n Wup))``.
For the others: ``s = sigmoid(n Wr)`` over all ``router_outputs`` experts,
the ``num_experts_per_tok`` largest of ``s + b`` selected (``b`` the
selection bias: it selects only, and is 0 in the compared step), weights
``routed_scaling_factor * s / (sum of the selected s + 1e-6)``, output ``sum
over the selected experts that are held of weight_e * E_e(n)``: a loop
over the held experts, every one on every token with the weight 0 where it
was not selected.  ``experts_held`` = all ``router_outputs`` gives the
uncut layer; a share leaves the other experts' part out, and that partial
result is what the next block sees.  No shared expert.

A final RMSNorm (the published code's ``embedding_norm``), logits through
the embedding's own matrix over the ``vocab_size`` rows held, and the summed
next-token cross-entropy.

Departures from the published description, none of which changes a number
beyond rounding: so that it fits beside the float32 weights and two
gradient-sized trees at published widths, every block is recomputed in the
backward pass and inside it the operator and the feed-forward each by
itself, a block takes its sequences one after another, attention walks its
queries in blocks of rows against the whole masked score row of each, and
the head walks its rows in blocks (``jax.checkpoint`` and blocking change
no arithmetic).  The published code keeps a cache of the last ``K - 1``
positions for decoding; training has none.

What the configuration file assumes (its ``assumed``: the q/k norms, the
thirds' order, the feed-forward's form, the tied head, the denominator's
1e-6, the bias's rule, the rotary pairing, plain SGD, initial values) lies
with that file.
"""

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
ROW_BLOCK = 512          # rows of the head a block
QUERY_BLOCK = 128        # query rows of the attention a block
DENOMINATOR_EPS = 1e-6   # the published router's, under the selected sum
# What a run in a coarser format would read (tools/chip_lfm2_probe.py sets
# them, nothing else does): the type every product's operands are rounded
# to first, and the router's alone.  None: float32 as it stands.
OPERANDS = None
ROUTER_OPERANDS = None


def _round(a, operands=None):
    operands = operands or OPERANDS
    if operands is None:
        return a
    return a.astype(operands).astype(jnp.float32)


def _mm(a, b, operands=None):
    return jnp.matmul(_round(a, operands), _round(b, operands),
                      precision=HI)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _block_of(n, most):
    """The largest block of at most ``most`` that divides n."""
    return max(r for r in range(1, min(n, most) + 1) if n % r == 0)


def _glu(n, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(n, w_gate)) * _mm(n, w_up), w_down)


def short_conv(p, n):
    """One sequence n (S, hidden) through a gated short convolution."""
    s, d = n.shape
    b, c, x = jnp.split(_mm(n, p["w_in"]), 3, axis=-1)
    u = b * x
    taps = p["conv_w"].shape[1]
    v = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j              # tap j reads u that far back
        shifted = jnp.concatenate([jnp.zeros((back, d), u.dtype),
                                   u[:s - back]]) if back else u
        v = v + p["conv_w"][:, j] * shifted
    return _mm(c * v, p["w_out"])


def _rope(x, theta):
    """x: (S, heads, head_dim), positions on the first axis; the pairs
    (x0, x1), (x2, x3), .. turned and written out as (first halves | second
    halves), as the program's ``split`` pairing writes them."""
    s, _, hd = x.shape
    inv = theta ** (-2.0 * jnp.arange(hd // 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention(p, n, c):
    """One sequence n (S, hidden): the queries in blocks of rows, each
    against its whole masked score row and recomputed in the backward
    pass."""
    s = n.shape[0]
    h, kv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    hd = int(c["hidden_size"]) // h
    eps, theta = float(c["norm_eps"]), float(c["rope_theta"])
    q = _rope(_rms(_mm(n, p["wq"]).reshape(s, h, hd), p["q_norm"], eps),
              theta)
    k = _rope(_rms(_mm(n, p["wk"]).reshape(s, kv, hd), p["k_norm"], eps),
              theta)
    v = _mm(n, p["wv"]).reshape(s, kv, hd)
    q = q.reshape(s, kv, h // kv, hd)
    rows = _block_of(s, QUERY_BLOCK)

    @jax.checkpoint
    def block(args):
        qb, first = args
        scores = jnp.einsum("qjgd,kjd->jgqk", _round(qb), _round(k),
                            precision=HI) / hd ** 0.5
        i = (first + jnp.arange(rows))[:, None]
        seen = jnp.arange(s)[None, :] <= i
        prob = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("jgqk,kjd->qjgd", _round(prob), _round(v),
                          precision=HI)

    out = lax.map(block, (q.reshape(s // rows, rows, kv, h // kv, hd),
                          jnp.arange(s // rows) * rows))
    return _mm(out.reshape(s, h * hd), p["wo"])


def router_weights(kernel, n, c, bias=0.0):
    """(tokens, router_outputs) combine weights, 0 where not selected."""
    score = jax.nn.sigmoid(_mm(n, kernel, ROUTER_OPERANDS))
    _, chosen = lax.top_k(lax.stop_gradient(score) + bias,
                          int(c["num_experts_per_tok"]))
    mask = jnp.sum(jax.nn.one_hot(chosen, score.shape[-1]), axis=-2)
    picked = score * mask
    return float(c["routed_scaling_factor"]) * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + DENOMINATOR_EPS)


def routed_part(experts, weights, n, c):
    """sum over the held experts of weight_e * E_e(n), one expert after
    another (``lax.scan`` over the stack: one expert's code and
    intermediates, whatever their number)."""
    lo, hi = (int(v) for v in c["experts_held"])

    @jax.checkpoint
    def add_one(out, expert):
        w_gate, w_up, w_down, weight = expert
        return out + weight[:, None] * _glu(n, w_gate, w_up, w_down), None

    out, _ = lax.scan(add_one, jnp.zeros_like(n),
                      (experts["w_gate"], experts["w_up"],
                       experts["w_down"], weights[:, lo:hi].T))
    return out


def feed_forward(p, i, n, c):
    """One sequence's normed rows n (S, hidden) through layer i's
    feed-forward."""
    if i < int(c["num_dense_layers"]):
        return _glu(n, **p[f"blk{i}_ffn"])
    weights = router_weights(p[f"blk{i}_moe_router"]["kernel"], n, c)
    return routed_part(p[f"blk{i}_moe_experts"], weights, n, c)


def _after_operator(p, i, x, c):
    """x (batch, positions, hidden) with block i's convolution or
    attention added, one sequence after another, each recomputed in the
    backward pass."""
    eps = float(c["norm_eps"])

    @jax.checkpoint
    def operate(row):
        n = _rms(row, p[f"blk{i}_norm1"]["scale"], eps)
        if c["layer_types"][i] == "conv":
            return short_conv(p[f"blk{i}_conv"], n)
        return attention(p[f"blk{i}_attn_full"], n, c)

    return x + lax.map(operate, x)


def _normed_for_feed_forward(p, i, x, c):
    return _rms(x, p[f"blk{i}_norm2"]["scale"], float(c["norm_eps"]))


def _block(p, i, x, c):
    """Every sequence of x through block i; operator and feed-forward
    recomputed each by itself."""
    x = _after_operator(p, i, x, c)

    @jax.checkpoint
    def feed(row):
        return feed_forward(p, i, _normed_for_feed_forward(p, i, row, c), c)

    return x + lax.map(feed, x)


def hidden(params, tokens, config):
    """(batch, positions, hidden) after the final norm."""
    x = params["embed"]["table"][tokens]
    for i in range(int(config["num_layers"])):
        mine = {k: v for k, v in params.items() if k.startswith(f"blk{i}_")}
        x = jax.checkpoint(
            lambda p, x, i=i: _block(p, i, x, config))(mine, x)
    return _rms(x, params["final_norm"]["scale"], float(config["norm_eps"]))


def router_selections(params, tokens, config):
    """(expert layers, batch, positions, router_outputs) booleans: which
    experts each token of each expert layer selected."""
    x = params["embed"]["table"][tokens]
    picked = []
    for i in range(int(config["num_layers"])):
        mine = {k: v for k, v in params.items() if k.startswith(f"blk{i}_")}
        x = _after_operator(mine, i, x, config)
        n = _normed_for_feed_forward(mine, i, x, config)
        if i >= int(config["num_dense_layers"]):
            picked.append(lax.map(lambda rows, i=i: router_weights(
                mine[f"blk{i}_moe_router"]["kernel"], rows, config) > 0, n))
        x = x + lax.map(lambda rows, i=i: feed_forward(
            mine, i, rows, config), n)
    return jnp.stack(picked)


def _nll_sum(table, x, targets, counted):
    """Summed cross-entropy of the ``counted`` rows of x (N, d) against
    targets (N,), logits through the embedding's own matrix, the rows in
    blocks so that the (N, vocabulary) logits never stand whole."""
    n = x.shape[0]
    rows = _block_of(n, ROW_BLOCK)

    @jax.checkpoint
    def block(args):
        xb, tb, cb = args
        lp = jax.nn.log_softmax(_mm(xb, table.T), axis=-1)
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
                        counted.reshape(-1).astype(jnp.float32))

    loss, grads = jax.value_and_grad(f)(params)
    return loss, grads, b * (s - 1)
