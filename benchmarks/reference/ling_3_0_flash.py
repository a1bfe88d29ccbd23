"""Ling-3.0-flash (inclusionAI; ``model_type`` ``bailing_hybrid``), or the
share of it that one chip holds, in plain ``jax.numpy`` and float32.
RMSNorm, no bias anywhere, an untied head; pre-norm blocks:

    h = x + Mixer_l(RMSNorm1(x));   y = h + FFN_l(RMSNorm2(h))

``Mixer_l`` is latent attention where ``(l + 1) % layer_group_size == 0``
and Kimi Delta Attention elsewhere.

KDA: ``[q | k | v | f | z | b] = n W_in``; q, k and v each through a
causal depthwise convolution of ``short_conv_kernel_size`` taps and SiLU;
q and k of unit length a head; ``g = kda_lower_bound * sigmoid(exp(A_log)
* (f + dt_bias))``, ``beta = sigmoid(b)``.  Then the recurrence, position
by position:

    S_t = Diag(exp(g_t)) S_{t-1};   S_t += beta_t k_t (v_t - k_t^T S_t)
    o_t = S_t^T q_t / sqrt(head_dim)

which is ``(I - beta k k^T) Diag(exp(g)) S + beta k v^T``; a ``lax.scan``
over the positions, recomputed in spans of ``KDA_SPAN`` in the backward
pass.  Each head's ``o`` through an RMSNorm with one gain for all heads,
times ``sigmoid(z)``, then ``W_out``.

MLA: ``q = n Wq`` (heads of ``[nope | rope]``); ``[c | k_pe] = n Wkva``;
``[k_nope | v] = RMSNorm(c) Wkvb`` a head; ``k = [k_nope | k_pe]``, the one
``k_pe`` given to every head; an RMSNorm over each head of q and of k
(``q_norm``, ``k_norm``), then rotary positions on the last ``rope``
dimensions (de-interleaved, the halves turned against each other);
causal softmax of ``q k^T / sqrt(nope + rope)``, the queries in blocks of
rows; each head's result times ``sigmoid(n Wg)``; ``Wo``.

``FFN_l`` of the first ``first_k_dense_replace`` blocks:
``Wdown(silu(n Wgate) * (n Wup))``.  Of the others: ``s = sigmoid(n Wr)``;
the ``n_group`` groups of consecutive experts ranked by the sum of their
two largest ``s + bias``; the ``num_experts_per_tok`` largest ``s + bias``
among the experts of the ``topk_group`` best groups selected; weights
``routed_scaling_factor * s / sum of the selected s``; output ``Shared(n) +
sum over the selected experts that are held of weight_e * E_e(n)``.  The
selection bias is the layer's state and starts at 0, which is what a
comparison from fresh state sees, so it is 0 here.  ``experts_held`` = all
``router_outputs`` gives the uncut layer; a share leaves the other
experts' part out, and that partial result is what the next block sees.

A final RMSNorm, the head over the ``vocab_size`` rows held, and the summed
next-token cross-entropy, the rows in blocks.  Every block is recomputed
in the backward pass, and inside it each KDA stream by itself
(``jax.checkpoint`` and blocking change no arithmetic).  What the
configuration file assumes lies with that file.
"""

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
ROW_BLOCK = 512          # rows of the head a block
QUERY_BLOCK = 128        # query rows of the attention a block
KDA_SPAN = 64            # positions of the recurrence recomputed together
# What a run in a coarser format would read (tools/chip_lfm2_probe.py
# --config ling_3_0_flash sets them, nothing else does): the type every
# product's operands are rounded to first, and the router's alone.  None:
# float32 as it stands.
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


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_recurrence(q, k, v, g, beta):
    """One sequence: q, k (S, H, K), v (S, H, V), g (S, H, K) log-decays,
    beta (S, H) -> o (S, H, V), position after position."""
    s, h, dk = q.shape

    def step(state, x):
        qt, kt, vt, gt, bt = x
        state = state * jnp.exp(gt)[..., None]
        pred = jnp.einsum("hk,hkv->hv", _round(kt), _round(state),
                          precision=HI)
        state = state + jnp.einsum("hk,hv->hkv", _round(bt[:, None] * kt),
                                   _round(vt - pred), precision=HI)
        return state, jnp.einsum("hk,hkv->hv", _round(qt), _round(state),
                                 precision=HI)

    span = _block_of(s, KDA_SPAN)

    @jax.checkpoint
    def walk(state, xs):
        return lax.scan(step, state, xs)

    xs = tuple(a.reshape((s // span, span) + a.shape[1:])
               for a in (q, k, v, g, beta))
    _, o = lax.scan(walk, jnp.zeros((h, dk, v.shape[-1]), jnp.float32), xs)
    return o.reshape(s, h, -1)


def _causal_conv(x, w):
    """x (S, C), w (C, taps): ``y_t = sum_j w[:, j] x_{t - taps + 1 + j}``,
    zeros before the sequence."""
    taps = w.shape[1]
    xp = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x])
    return sum(w[:, j] * xp[j:j + x.shape[0]] for j in range(taps))


def kda(p_in, p_out, n, c):
    """One sequence's normed rows n (S, hidden) through a KDA mixer; each
    of q, k, v, the decays, the write strengths and the output gate, and
    what comes after the recurrence, recomputed by itself in the
    backward pass."""
    s = n.shape[0]
    h, hd = int(c["num_attention_heads"]), int(c["head_dim"])
    di = h * hd
    w = p_in["w_in"]
    part = lambda i, width=di: w[:, i * di:i * di + width]

    @jax.checkpoint
    def convolved(w, conv_w, n):
        return jax.nn.silu(_causal_conv(_mm(n, w), conv_w)).reshape(
            s, h, hd)

    @jax.checkpoint
    def decays(w, a_log, dt_bias, n):
        rate = jnp.repeat(jnp.exp(a_log), hd)
        return float(c["kda_lower_bound"]) * jax.nn.sigmoid(
            rate * (_mm(n, w) + dt_bias)).reshape(s, h, hd)

    @jax.checkpoint
    def after(p_out, o, w_z, n):
        o = _rms(o, p_out["norm"], float(c["rms_norm_eps"])) \
            * jax.nn.sigmoid(_mm(n, w_z).reshape(s, h, hd))
        return _mm(o.reshape(s, di), p_out["w_out"])

    q, k, v = (convolved(part(i), p_in["conv_w"][i * di:(i + 1) * di], n)
               for i in range(3))
    g = decays(part(3), p_in["A_log"], p_in["dt_bias"], n)
    beta = jax.nn.sigmoid(_mm(n, part(5, h)))
    o = kda_recurrence(_unit(q) / hd ** 0.5, _unit(k), v, g, beta)
    return after(p_out, o, part(4), n)


def _rope(x, theta):
    """x: (S, heads, d) with positions on the first axis: the dimensions
    de-interleaved and the two halves rotated against each other."""
    s, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None] * inv)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)


def mla(p, n, c):
    """One sequence's normed rows n (S, hidden) through latent attention
    with its head norms and head gate, the queries in blocks of rows,
    each against its whole masked score row."""
    s = n.shape[0]
    h = int(c["num_attention_heads"])
    nope, rope = int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"])
    rank, vd = int(c["kv_lora_rank"]), int(c["v_head_dim"])
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    q = _mm(n, p["wq"]).reshape(s, h, nope + rope)
    ckv = _mm(n, p["wkva"])
    kv = _mm(_rms(ckv[:, :rank], p["kv_norm"], eps), p["wkvb"]).reshape(
        s, h, nope + vd)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        ckv[:, None, rank:], (s, h, rope))], axis=-1)
    q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    q, k = (jnp.concatenate([y[..., :nope], _rope(y[..., nope:], theta)],
                            axis=-1) for y in (q, k))
    v = kv[..., nope:]
    rows = _block_of(s, QUERY_BLOCK)

    @jax.checkpoint
    def block(args):
        qb, first = args
        scores = jnp.einsum("qhd,khd->hqk", _round(qb), _round(k),
                            precision=HI) / (nope + rope) ** 0.5
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(s)[None, :]
        prob = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _round(prob), _round(v),
                          precision=HI)

    out = lax.map(block, (q.reshape(s // rows, rows, h, nope + rope),
                          jnp.arange(s // rows) * rows))
    out = out.reshape(s, h, vd) * jax.nn.sigmoid(_mm(n, p["wg"]))[..., None]
    return _mm(out.reshape(s, h * vd), p["wo"])


def _glu(n, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(n, w_gate)) * _mm(n, w_up), w_down)


def router_weights(kernel, n, c, bias=0.0):
    """(tokens, router_outputs) combine weights, 0 where not selected:
    the top k of the experts in the best groups."""
    score = jax.nn.sigmoid(_mm(n, kernel, ROUTER_OPERANDS))
    ranked = lax.stop_gradient(score) + bias
    tokens, experts = ranked.shape
    groups = int(c["n_group"])
    by_group = ranked.reshape(tokens, groups, experts // groups)
    best_two = jnp.sort(by_group, axis=-1)[..., -2:].sum(axis=-1)
    _, kept = lax.top_k(best_two, int(c["topk_group"]))
    kept_group = jnp.any(jnp.arange(groups)[None, :, None]
                         == kept[:, None, :], axis=-1)      # (tokens, groups)
    in_kept = jnp.repeat(kept_group, experts // groups, axis=-1)
    _, chosen = lax.top_k(jnp.where(in_kept, ranked, -jnp.inf),
                          int(c["num_experts_per_tok"]))
    mask = jnp.sum(jax.nn.one_hot(chosen, experts), axis=-2)
    picked = score * mask
    return float(c["routed_scaling_factor"]) * picked / jnp.sum(
        picked, axis=-1, keepdims=True)


def routed_part(experts, weights, n, c):
    """sum over the held experts of weight_e * E_e(n): every held expert
    on every token, the weight 0 where it was not selected, one expert at
    a time (``lax.scan`` over the stack)."""
    lo, hi = (int(v) for v in c["experts_held"])

    @jax.checkpoint
    def add_one(out, expert):
        w_gate, w_up, w_down, weight = expert
        return out + weight[:, None] * _glu(n, w_gate, w_up, w_down), None

    out, _ = lax.scan(add_one, jnp.zeros_like(n),
                      (experts["w_gate"], experts["w_up"],
                       experts["w_down"], weights[:, lo:hi].T))
    return out


def _is_mla(c, i):
    return (i + 1) % int(c["layer_group_size"]) == 0


def _mixed(p, i, x, c):
    """One sequence's x with block i's mixer added."""
    n = _rms(x, p[f"blk{i}_norm1"]["scale"], float(c["rms_norm_eps"]))
    if _is_mla(c, i):
        return x + mla(p[f"blk{i}_mla"], n, c)
    return x + kda(p[f"blk{i}_kda_in"], p[f"blk{i}_kda_out"], n, c)


def _block(p, i, x, c):
    """One sequence through block i.  ``p`` holds the block's ops."""
    h = _mixed(p, i, x, c)
    n = _rms(h, p[f"blk{i}_norm2"]["scale"], float(c["rms_norm_eps"]))
    if i < int(c["first_k_dense_replace"]):
        return h + _glu(n, **p[f"blk{i}_ffn"])
    weights = router_weights(p[f"blk{i}_moe_router"]["kernel"], n, c)
    return h + _glu(n, **p[f"blk{i}_moe_shared"]) + routed_part(
        p[f"blk{i}_moe_experts"], weights, n, c)


def _layers(params, config):
    for i in range(int(config["num_layers"])):
        yield i, {k: v for k, v in params.items()
                  if k.startswith(f"blk{i}_")}


def hidden(params, tokens, config):
    """(batch, positions, hidden) after the final norm."""
    def one(toks):
        x = params["embed"]["table"][toks]
        for i, mine in _layers(params, config):
            x = jax.checkpoint(
                lambda p, x, i=i: _block(p, i, x, config))(mine, x)
        return _rms(x, params["final_norm"]["scale"],
                    float(config["rms_norm_eps"]))

    return jax.vmap(one)(tokens)


def router_selections(params, tokens, config):
    """(expert layers, batch, positions, router_outputs) booleans: which
    experts each token of each expert layer selected."""
    eps = float(config["rms_norm_eps"])

    def one(toks):
        x = params["embed"]["table"][toks]
        picked = []
        for i, mine in _layers(params, config):
            if i >= int(config["first_k_dense_replace"]):
                n = _rms(_mixed(mine, i, x, config),
                         mine[f"blk{i}_norm2"]["scale"], eps)
                picked.append(router_weights(
                    mine[f"blk{i}_moe_router"]["kernel"], n, config) > 0)
            x = _block(mine, i, x, config)
        return jnp.stack(picked)

    return jnp.swapaxes(jax.vmap(one)(tokens), 0, 1)


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
