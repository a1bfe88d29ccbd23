"""SmallThinker-21BA3B-Instruct (PowerInfer; ``model_name``
``smallthinker_21b_instruct``), or the share of it that one chip holds, in
plain ``jax.numpy`` and float32.  RMSNorm (eps 1e-6), no bias anywhere, no
shared expert, an untied head; every block routes before attention:

    n = RMSNorm1(x);   w = Router(n)
    h = x + Attn_l(n);   y = h + sum_e w_e E_e(RMSNorm2(h))

``Attn_l`` with ``num_attention_heads`` query heads of ``head_dim`` on
``num_key_value_heads`` key-value heads: ``q = n Wq``, ``k = n Wk``,
``v = n Wv``; where ``rope_layout[l]`` is 1, rotary positions on q and k
(theta ``rope_theta``, all of a head's dimensions, frequencies
``theta^(-2i/dim)``, the even and the odd dimensions turned against each
other and written as two halves); where it is 0, none.  Scores ``q k^T /
sqrt(head_dim)``, query head ``j`` reading key-value head ``j // (H /
KV)``; key ``t`` is seen by query ``i`` when ``t <= i`` and, where
``sliding_window_layout[l]`` is 1, ``i - t < sliding_window_size``.

The router: ``p = softmax(n Wr)`` over all ``router_outputs`` experts, the
``moe_num_active_primary_experts`` largest selected, weights ``p / sum of
the selected p``, read from ``n``, the attention's own input.  An expert:
``Wdown(relu(m Wgate) * (m Wup))`` on ``m = RMSNorm2(h)``; a loop over the
held experts, every one on every token with the weight 0 where it was not
selected.  ``experts_held`` = all ``router_outputs`` gives the uncut
layer; a share leaves the other experts' part out, and that partial result
is what the next block sees.

A final RMSNorm, the head over the ``vocab_size`` rows held, and the summed
next-token cross-entropy.

So that it fits beside the float32 weights and two gradient-sized trees at
published widths (16 384 positions, 28 heads: one sequence's whole score
matrix would be 30 GB): every block is recomputed in the backward pass and
inside it the attention and the expert layer each by itself, a block takes
its sequences one after another, attention walks its queries in blocks of
rows against the whole masked score row of each, and the head walks its
rows in blocks (``jax.checkpoint`` and blocking change no arithmetic).

What the configuration file assumes (its ``assumed``: the router's input,
ReGLU, no secondary experts, no bias or q/k norm, the window's edge, the
rotary pairing, plain SGD, initial values) lies with that file.
"""

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
ROW_BLOCK = 512          # rows of the head a block
QUERY_BLOCK = 128        # query rows of the attention a block
# What a run in a coarser format would read (tools/chip_lfm2_probe.py
# --config smallthinker_21b_a3b sets them, nothing else does): the type
# every product's operands are rounded to first, and the router's alone.  None: float32 as it stands.
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


def _rope(x, theta):
    """x: (S, heads, head_dim), positions on the first axis."""
    s, _, hd = x.shape
    inv = theta ** (-2.0 * jnp.arange(hd // 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention(p, n, c, layer):
    """One sequence's normed rows n (S, hidden) through layer ``layer``'s
    attention: the queries in blocks of rows, each against its whole
    masked score row and recomputed in the backward pass."""
    s = n.shape[0]
    h, kv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    hd = int(c["head_dim"])
    window = int(c["sliding_window_size"]) \
        if c["sliding_window_layout"][layer] else s
    q = _mm(n, p["wq"]).reshape(s, h, hd)
    k = _mm(n, p["wk"]).reshape(s, kv, hd)
    v = _mm(n, p["wv"]).reshape(s, kv, hd)
    if c["rope_layout"][layer]:
        q, k = (_rope(y, float(c["rope_theta"])) for y in (q, k))
    q = q.reshape(s, kv, h // kv, hd)
    rows = _block_of(s, QUERY_BLOCK)

    @jax.checkpoint
    def block(args):
        qb, first = args
        scores = jnp.einsum("qjgd,kjd->jgqk", _round(qb), _round(k),
                            precision=HI) / hd ** 0.5
        i = (first + jnp.arange(rows))[:, None]
        t = jnp.arange(s)[None, :]
        seen = (t <= i) & (i - t < window)
        prob = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("jgqk,kjd->qjgd", _round(prob), _round(v),
                          precision=HI)

    out = lax.map(block, (q.reshape(s // rows, rows, kv, h // kv, hd),
                          jnp.arange(s // rows) * rows))
    return _mm(out.reshape(s, h * hd), p["wo"])


def router_weights(kernel, n, c):
    """(tokens, router_outputs) combine weights, 0 where not selected."""
    prob = jax.nn.softmax(_mm(n, kernel, ROUTER_OPERANDS), axis=-1)
    _, chosen = lax.top_k(lax.stop_gradient(prob),
                          int(c["moe_num_active_primary_experts"]))
    mask = jnp.sum(jax.nn.one_hot(chosen, prob.shape[-1]), axis=-2)
    picked = prob * mask
    return picked / jnp.sum(picked, axis=-1, keepdims=True)


def routed_part(experts, weights, m, c):
    """sum over the held experts of weight_e * E_e(m), E_e ReGLU, one
    expert after another (``lax.scan`` over the stack: one expert's code
    and intermediates, whatever their number)."""
    lo, hi = (int(v) for v in c["experts_held"])

    @jax.checkpoint
    def add_one(out, expert):
        w_gate, w_up, w_down, weight = expert
        y = _mm(jax.nn.relu(_mm(m, w_gate)) * _mm(m, w_up), w_down)
        return out + weight[:, None] * y, None

    out, _ = lax.scan(add_one, jnp.zeros_like(m),
                      (experts["w_gate"], experts["w_up"],
                       experts["w_down"], weights[:, lo:hi].T))
    return out


def _attn_name(c, i):
    return f"blk{i}_attn_" + ("window" if c["sliding_window_layout"][i]
                              else "full")


def _normed(p, i, which, x, c):
    return _rms(x, p[f"blk{i}_{which}"]["scale"], float(c["rms_norm_eps"]))


def _gates(p, i, x, c):
    """Each sequence's combine weights from the block's input x (batch,
    positions, hidden): the router reads what attention reads."""
    return lax.map(lambda row: router_weights(
        p[f"blk{i}_moe_router"]["kernel"], _normed(p, i, "norm1", row, c),
        c), x)


def _after_attention(p, i, x, c):
    """x with block i's attention added, one sequence after another, each
    recomputed in the backward pass."""
    @jax.checkpoint
    def attend(row):
        return attention(p[_attn_name(c, i)],
                         _normed(p, i, "norm1", row, c), c, i)

    return x + lax.map(attend, x)


def _block(p, i, x, c):
    """Every sequence of x through block i; attention and the expert
    layer recomputed each by itself."""
    gates = _gates(p, i, x, c)
    h = _after_attention(p, i, x, c)

    @jax.checkpoint
    def experts(args):
        row, weights = args
        return routed_part(p[f"blk{i}_moe_experts"], weights,
                           _normed(p, i, "norm2", row, c), c)

    return h + lax.map(experts, (h, gates))


def _layers(params, config):
    for i in range(int(config["num_layers"])):
        yield i, {k: v for k, v in params.items()
                  if k.startswith(f"blk{i}_")}


def hidden(params, tokens, config):
    """(batch, positions, hidden) after the final norm."""
    x = params["embed"]["table"][tokens]
    for i, mine in _layers(params, config):
        x = jax.checkpoint(
            lambda p, x, i=i: _block(p, i, x, config))(mine, x)
    return _rms(x, params["final_norm"]["scale"],
                float(config["rms_norm_eps"]))


def router_selections(params, tokens, config):
    """(layers, batch, positions, router_outputs) booleans: which experts
    each token of each layer selected."""
    x = params["embed"]["table"][tokens]
    picked = []
    for i, mine in _layers(params, config):
        picked.append(_gates(mine, i, x, config) > 0)
        x = _block(mine, i, x, config)
    return jnp.stack(picked)


def _nll_sum(head, x, targets, counted):
    """Summed cross-entropy of the ``counted`` rows of x (N, d) against
    targets (N,), the rows in blocks so that the (N, vocabulary) logits
    never stand whole."""
    n = x.shape[0]
    rows = _block_of(n, ROW_BLOCK)

    @jax.checkpoint
    def block(args):
        xb, tb, cb = args
        lp = jax.nn.log_softmax(_mm(xb, head), axis=-1)
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
        return _nll_sum(p["lm_head"]["kernel"], x.reshape(b * s, -1),
                        targets.reshape(-1),
                        counted.reshape(-1).astype(jnp.float32))

    loss, grads = jax.value_and_grad(f)(params)
    return loss, grads, b * (s - 1)
