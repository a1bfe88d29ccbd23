"""Moonlight-16B-A3B (moonshotai, arXiv:2502.16982; ``model_type``
``deepseek_v3``), or the share of it that one chip holds, in plain
``jax.numpy`` and float32.  Pre-norm blocks, no bias anywhere:

    h = x + MLA(RMSNorm(x));   y = h + FFN(RMSNorm(h))

``MLA``: ``q = n Wq`` (heads of ``[nope | rope]``); ``[c | k_pe] = n Wkva``;
``[k_nope | v] = RMSNorm(c) Wkvb`` a head; rotary positions on every
head's ``q_pe`` and on the one ``k_pe`` all heads share; causal softmax of
``q k^T / sqrt(nope + rope)``; ``Wo``.  ``FFN`` of the first
``first_k_dense_replace`` blocks: ``Wdown(silu(n Wgate) * (n Wup))``.  Of the
others: ``s = sigmoid(n Wr)``, the ``num_experts_per_tok`` largest of
``s + b`` selected, weights ``routed_scaling_factor * s / sum of the
selected s``, output ``Shared(n) + sum over the selected experts that are
held of weight_e * E_e(n)``.  ``experts_held`` = all ``router_outputs``
gives the uncut model; a share leaves the other experts' part out, and
that partial result is what the next block sees.  The selection bias b is
the layer's state and starts at 0, which is what a comparison from fresh
state sees, so it is taken as 0 here.  A final RMSNorm, an untied head
over the ``vocab_size`` rows held, and the summed next-token
cross-entropy.

So that it fits beside three float32 copies of the weights at published
widths: every block and the head are recomputed in the backward pass
(``jax.checkpoint`` changes no arithmetic), and attention and the head
walk their rows in blocks.

Departures from the published model are the configuration file's
``assumed``: plain SGD outside this file, no balance loss.
"""

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
ROW_BLOCK = 512
# What a run in a coarser format would read (tools/chip_moonlight_probe.py
# sets them, nothing else does): the type every product's operands are
# rounded to first, and the router's alone.  None: float32 as it stands.
OPERANDS = None
ROUTER_OPERANDS = None


def _mm(a, b, operands=None):
    operands = operands or OPERANDS
    if operands is not None:
        a, b = (x.astype(operands).astype(jnp.float32) for x in (a, b))
    return jnp.matmul(a, b, precision=HI)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (S, .., d) with positions on the first axis: the published
    code's pairing, the dimensions de-interleaved and the two halves
    rotated against each other."""
    s, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    ra = a * jnp.cos(ang) - b * jnp.sin(ang)
    rb = a * jnp.sin(ang) + b * jnp.cos(ang)
    return jnp.concatenate([ra, rb], axis=-1)


def _rows(n):
    """The largest block of at most ROW_BLOCK rows that divides n."""
    return max(r for r in range(1, min(n, ROW_BLOCK) + 1) if n % r == 0)


def _attend(q, k, v):
    """One sequence: q, k (S, H, dq), v (S, H, dv) -> (S, H, dv); the
    queries in blocks of rows, each recomputed in the backward pass."""
    s, h, dq = q.shape
    rows = _rows(s)

    @jax.checkpoint
    def block(args):
        qb, first = args
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / dq ** 0.5
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = lax.map(block, (q.reshape(s // rows, rows, h, dq),
                          jnp.arange(s // rows) * rows))
    return out.reshape(s, h, -1)


def _mla(p, n, c):
    s = n.shape[0]
    h = int(c["num_attention_heads"])
    nope, rope = int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"])
    rank, vd = int(c["kv_lora_rank"]), int(c["v_head_dim"])
    theta = float(c["rope_theta"])
    q = _mm(n, p["wq"]).reshape(s, h, nope + rope)
    ckv = _mm(n, p["wkva"])
    latent = _rms(ckv[:, :rank], p["kv_norm"], float(c["rms_norm_eps"]))
    kv = _mm(latent, p["wkvb"]).reshape(s, h, nope + vd)
    q = jnp.concatenate([q[..., :nope],
                         _rope(q[..., nope:], theta)], axis=-1)
    k_pe = _rope(ckv[:, rank:], theta)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe[:, None, :], (s, h, rope))],
                        axis=-1)
    out = _attend(q, k, kv[..., nope:])
    return _mm(out.reshape(s, h * vd), p["wo"])


def _glu(n, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(n, w_gate)) * _mm(n, w_up), w_down)


def router_weights(kernel, n, c, bias=0.0):
    """(tokens, router_outputs) combine weights, 0 where not selected."""
    score = jax.nn.sigmoid(_mm(n, kernel, ROUTER_OPERANDS))
    _, chosen = lax.top_k(lax.stop_gradient(score) + bias,
                          int(c["num_experts_per_tok"]))
    mask = jnp.sum(jax.nn.one_hot(chosen, score.shape[-1]), axis=-2)
    picked = score * mask
    return float(c["routed_scaling_factor"]) * picked / jnp.sum(
        picked, axis=-1, keepdims=True)


def routed_part(experts, weights, n, c):
    """sum over the held experts of weight_e * E_e(n): every held expert
    on every token, the weight 0 where it was not selected.  One expert
    at a time (``lax.scan`` over the stack: one expert's code and
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


def _block(p, i, x, c):
    """One sequence through block i.  ``p`` holds the block's ops."""
    eps = float(c["rms_norm_eps"])
    h = x + _mla(p[f"blk{i}_mla"], _rms(x, p[f"blk{i}_norm1"]["scale"], eps),
                 c)
    n = _rms(h, p[f"blk{i}_norm2"]["scale"], eps)
    if i < int(c["first_k_dense_replace"]):
        return h + _glu(n, **p[f"blk{i}_ffn"])
    weights = router_weights(p[f"blk{i}_moe_router"]["kernel"], n, c)
    return h + _glu(n, **p[f"blk{i}_moe_shared"]) + routed_part(
        p[f"blk{i}_moe_experts"], weights, n, c)


def _through_blocks(params, toks, config, visit=None):
    """One sequence through every block; ``visit(i, x)`` sees each
    block's input."""
    x = params["embed"]["table"][toks]
    for i in range(int(config["num_layers"])):
        if visit is not None:
            visit(i, x)
        mine = {k: v for k, v in params.items() if k.startswith(f"blk{i}_")}
        x = jax.checkpoint(
            lambda p, x, i=i: _block(p, i, x, config))(mine, x)
    return x


def hidden(params, tokens, config):
    """(batch, positions, hidden) after the final norm."""
    def one(toks):
        return _rms(_through_blocks(params, toks, config),
                    params["final_norm"]["scale"],
                    float(config["rms_norm_eps"]))

    return jax.vmap(one)(tokens)


def router_selections(params, tokens, config):
    """(expert layers, batch, positions, router_outputs) booleans: which
    experts each token of each expert layer selected."""
    eps = float(config["rms_norm_eps"])

    def one(toks):
        picked = []

        def visit(i, x):
            if i < int(config["first_k_dense_replace"]):
                return
            h = x + _mla(params[f"blk{i}_mla"],
                         _rms(x, params[f"blk{i}_norm1"]["scale"], eps),
                         config)
            n = _rms(h, params[f"blk{i}_norm2"]["scale"], eps)
            picked.append(router_weights(
                params[f"blk{i}_moe_router"]["kernel"], n, config) > 0)

        _through_blocks(params, toks, config, visit)
        return jnp.stack(picked)

    return jnp.swapaxes(jax.vmap(one)(tokens), 0, 1)


def _nll_sum(head, x, targets, counted):
    """Summed cross-entropy of the ``counted`` rows of x (N, d) against
    targets (N,), the rows in blocks so that the (N, vocabulary) logits
    never stand whole."""
    n = x.shape[0]
    rows = _rows(n)

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
