"""GPT-2 (Radford et al. 2019; sizes from the public ``gpt2``
``config.json``): token + learned position embeddings, ``n_layer``
pre-norm blocks (LayerNorm, causal multi-head attention, residual;
LayerNorm, 4x feed-forward with the tanh GELU, residual), a final
LayerNorm and a projection to the vocabulary; next-token cross-entropy.

Departures from the published model, which the configuration file lists
under ``assumed``: the output head is a separate matrix with a bias
(not tied to the embedding), and the q/k/v projections carry no bias
(the output projection does).  ``log_probs`` is the full forward pass
a serve cell's comparison would use; training takes loss and gradients.
"""

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def _ln(p, x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _attn(p, x, heads):
    b, s, d = x.shape
    hd = d // heads
    split = lambda w: jnp.einsum("bsd,de->bse", x, w, precision=HI) \
        .reshape(b, s, heads, hd).transpose(0, 2, 1, 3)
    q, k, v = split(p["wq"]), split(p["wk"]), split(p["wv"])
    scores = jnp.einsum("bhqe,bhke->bhqk", q, k, precision=HI) / hd ** 0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bhke->bhqe", jax.nn.softmax(scores, -1), v,
                     precision=HI)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, d)
    return jnp.einsum("bsd,de->bse", out, p["wo"], precision=HI) + p["bo"]


def _lin(p, x):
    return jnp.einsum("bsd,de->bse", x, p["kernel"], precision=HI) \
        + p["bias"]


def logits(p, tokens, config):
    eps = float(config["layer_norm_epsilon"])
    s = tokens.shape[1]
    x = p["embed"]["table"][tokens] + p["pos_embed"]["table"][:s]
    for i in range(int(config["n_layer"])):
        x = x + _attn(p[f"blk{i}_attn"], _ln(p[f"blk{i}_ln1"], x, eps),
                      int(config["n_head"]))
        h = _lin(p[f"blk{i}_ff1"], _ln(p[f"blk{i}_ln2"], x, eps))
        x = x + _lin(p[f"blk{i}_ff2"], jax.nn.gelu(h, approximate=True))
    return _lin(p["lm_head"], _ln(p["final_ln"], x, eps))


def log_probs(params, tokens, config):
    """(batch, positions, vocabulary) log-probabilities of the full
    forward pass: what prefill and decoding through the cache must give
    at each sequence's last position."""
    return jax.nn.log_softmax(logits(params, tokens, config), axis=-1)


def sum_loss_and_grads(params, batch, config):
    """Position i predicts token i+1; the last position has no target."""
    tokens, labels = batch

    def f(p):
        lp = log_probs(p, tokens, config)[:, :-1]
        return -jnp.sum(jnp.take_along_axis(lp, labels[:, 1:, None],
                                            axis=-1))

    loss, grads = jax.value_and_grad(f)(params)
    return loss, grads, tokens.shape[0] * (tokens.shape[1] - 1)
