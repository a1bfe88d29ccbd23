"""Laguna-S-2.1 (poolside; ``model_type`` ``laguna``), or the share of it
that one chip holds, in plain ``jax.numpy`` and float32.  Pre-norm blocks,
RMSNorm (eps 1e-6), no bias anywhere, an untied head:

    h = x + Attn_l(RMSNorm(x));   y = h + FF_l(RMSNorm(h))

``Attn_l`` with ``H = num_attention_heads_per_layer[l]`` query heads of
``head_dim`` on ``num_key_value_heads`` key-value heads: ``q = n Wq``,
``k = n Wk``, ``v = n Wv``; rotary positions on q and k by the layer's type
(``rope_parameters[layer_types[l]]``): the leading ``partial_rotary_factor``
of a head's dimensions turn, the rest pass through; frequencies
``theta^(-2i/dim)`` under ``rope_type`` ``default`` and, under ``yarn``,
written out below as ``transformers``' ``_compute_yarn_parameters`` computes
them, cos and sin times ``attention_factor``.  Scores ``q k^T /
sqrt(head_dim)``, query head ``j`` reading key-value head ``j // (H / KV)``;
key ``t`` is seen by query ``i`` when ``t <= i`` and, on a
``sliding_attention`` layer, ``i - t < sliding_window``.  The head's result
is multiplied by ``sigmoid(n Wg)[head]`` (one number a head and token)
before ``Wo``.

``FF_l`` of a ``dense`` layer: ``Wdown(silu(n Wgate) * (n Wup))``.  Of a
``sparse`` one: ``p = softmax(n Wr)`` over all ``router_outputs`` experts,
the ``num_experts_per_tok`` largest selected, weights
``moe_routed_scaling_factor * p / sum of the selected p``, output
``Shared(n) + sum over the selected experts that are held of weight_e *
E_e(n)``: a loop over the held experts, every one on every token with the
weight 0 where it was not selected.  ``experts_held`` = all
``router_outputs`` gives the uncut layer; a share leaves the other
experts' part out, and that partial result is what the next block sees.

A final RMSNorm, the head over the ``vocab_size`` rows held, and the summed
next-token cross-entropy.

So that it fits beside the float32 weights and two gradient-sized trees at
published widths (8192 positions, 72 heads: one sequence's whole score
matrix would be 19 GB): every block is recomputed in the backward pass and
inside it the attention and the feed-forward each by itself, a block takes
its sequences one after another, attention walks its queries in blocks of
rows against the whole masked score row of each, and the head walks its
rows in blocks (``jax.checkpoint`` and blocking change no arithmetic).

What the configuration file assumes (its ``assumed``: the router's rule,
the ungated shared expert, the gate's form, no q/k norm, the feed-forward's
form, the rotary pairing, plain SGD, initial values) lies with that file.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
ROW_BLOCK = 512          # rows of the head a block
QUERY_BLOCK = 64         # query rows of the attention a block
# What a run in a coarser format would read (tools/chip_laguna_probe.py
# sets them, nothing else does): the type every product's operands are
# rounded to first, and the router's alone.  None: float32 as it stands.
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


def rotary_frequencies(rule, head_dim):
    """(the angle a position turns pair i by, for the pairs that turn;
    what cos and sin are multiplied by)."""
    dim = int(head_dim * float(rule.get("partial_rotary_factor", 1)))
    theta = float(rule["rope_theta"])
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * i / dim)
    if rule.get("rope_type", "default") == "default":
        return plain, 1.0
    factor = float(rule["factor"])
    original = float(rule["original_max_position_embeddings"])

    def dimension_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dimension_of(float(rule["beta_fast"]))), 0)
    high = min(math.ceil(dimension_of(float(rule["beta_slow"]))), dim - 1)
    ramp = jnp.clip((i - low) / ((high if high != low else high + 0.001)
                                 - low), 0.0, 1.0)
    # below `low` a pair keeps its own frequency, above `high` it turns
    # `factor` times slower, between the two it is their blend
    return plain * (1.0 - ramp) + plain / factor * ramp, \
        float(rule["attention_factor"])


def _rope(x, rule):
    """x: (S, heads, head_dim), positions on the first axis."""
    s, _, hd = x.shape
    inv, grow = rotary_frequencies(rule, hd)
    turned = 2 * inv.shape[0]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = grow * jnp.cos(ang), grow * jnp.sin(ang)
    a, b = x[..., 0:turned:2], x[..., 1:turned:2]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., turned:]], axis=-1)


def _attention(p, n, c, layer):
    """One sequence n (S, hidden): the queries in blocks of rows, each
    against its whole masked score row and recomputed in the backward
    pass."""
    s = n.shape[0]
    h = int(c["num_attention_heads_per_layer"][layer])
    kv, hd = int(c["num_key_value_heads"]), int(c["head_dim"])
    kind = c["layer_types"][layer]
    window = int(c["sliding_window"]) if kind == "sliding_attention" else s
    rule = c["rope_parameters"][kind]
    q = _rope(_mm(n, p["wq"]).reshape(s, h, hd), rule)
    k = _rope(_mm(n, p["wk"]).reshape(s, kv, hd), rule)
    v = _mm(n, p["wv"]).reshape(s, kv, hd)
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
    gate = jax.nn.sigmoid(_mm(n, p["wg"]))                       # (S, H)
    out = out.reshape(s, h, hd) * gate[:, :, None]
    return _mm(out.reshape(s, h * hd), p["wo"])


def router_weights(kernel, n, c):
    """(tokens, router_outputs) combine weights, 0 where not selected."""
    prob = jax.nn.softmax(_mm(n, kernel, ROUTER_OPERANDS), axis=-1)
    _, chosen = lax.top_k(lax.stop_gradient(prob),
                          int(c["num_experts_per_tok"]))
    mask = jnp.sum(jax.nn.one_hot(chosen, prob.shape[-1]), axis=-2)
    picked = prob * mask
    return float(c["moe_routed_scaling_factor"]) * picked / jnp.sum(
        picked, axis=-1, keepdims=True)


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
    if c["mlp_layer_types"][i] == "dense":
        return _glu(n, **p[f"blk{i}_ffn"])
    weights = router_weights(p[f"blk{i}_moe_router"]["kernel"], n, c)
    return _glu(n, **p[f"blk{i}_moe_shared"]) + routed_part(
        p[f"blk{i}_moe_experts"], weights, n, c)


def _attn_name(c, i):
    return f"blk{i}_attn_" + ("window" if c["layer_types"][i]
                              == "sliding_attention" else "full")


def _after_attention(p, i, x, c):
    """x (batch, positions, hidden) with block i's attention added, one
    sequence after another, each recomputed in the backward pass."""
    @jax.checkpoint
    def attend(row):
        return _attention(
            p[_attn_name(c, i)], _rms(row, p[f"blk{i}_norm1"]["scale"],
                                      float(c["rms_norm_eps"])), c, i)

    return x + lax.map(attend, x)


def _normed_for_feed_forward(p, i, x, c):
    return _rms(x, p[f"blk{i}_norm2"]["scale"], float(c["rms_norm_eps"]))


def _block(p, i, x, c):
    """Every sequence of x through block i; attention and feed-forward
    recomputed each by itself."""
    x = _after_attention(p, i, x, c)

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
    return _rms(x, params["final_norm"]["scale"],
                float(config["rms_norm_eps"]))


def router_selections(params, tokens, config):
    """(expert layers, batch, positions, router_outputs) booleans: which
    experts each token of each expert layer selected."""
    x = params["embed"]["table"][tokens]
    picked = []
    for i in range(int(config["num_layers"])):
        mine = {k: v for k, v in params.items() if k.startswith(f"blk{i}_")}
        x = _after_attention(mine, i, x, config)
        n = _normed_for_feed_forward(mine, i, x, config)
        if config["mlp_layer_types"][i] != "dense":
            picked.append(lax.map(lambda rows, i=i: router_weights(
                mine[f"blk{i}_moe_router"]["kernel"], rows, config) > 0, n))
        x = x + lax.map(lambda rows, i=i: feed_forward(
            mine, i, rows, config), n)
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
