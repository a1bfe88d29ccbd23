"""Plain layers for the CNN references: float32, every contraction under
``Precision.HIGHEST``, no kernels, no sharding, nothing from
``flexflow_tpu``.  Layout NHWC, kernels HWIO, as the configurations
state."""

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def conv(p, x, stride=(1, 1), pad=(0, 0), relu=False):
    y = lax.conv_general_dilated(
        x, p["kernel"], window_strides=stride,
        padding=((pad[0], pad[0]), (pad[1], pad[1])),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)
    y = y + p["bias"]
    return jnp.maximum(y, 0.0) if relu else y


def _windows(k, s, p):
    return ((1, k[0], k[1], 1), (1, s[0], s[1], 1),
            ((0, 0), (p[0], p[0]), (p[1], p[1]), (0, 0)))


def max_pool(x, k, s, pad=(0, 0), relu=False):
    y = lax.reduce_window(x, -jnp.inf, lax.max, *_windows(k, s, pad))
    return jnp.maximum(y, 0.0) if relu else y


def avg_pool(x, k, s, pad=(0, 0), relu=False):
    """Mean over the positions of the window that lie inside the image
    (padding is not counted)."""
    w = _windows(k, s, pad)
    total = lax.reduce_window(x, 0.0, lax.add, *w)
    count = lax.reduce_window(jnp.ones((1,) + x.shape[1:3] + (1,), x.dtype),
                              0.0, lax.add, *w)
    y = total / count
    return jnp.maximum(y, 0.0) if relu else y


def linear(p, x, relu=False):
    y = jnp.dot(x, p["kernel"], precision=HI) + p["bias"]
    return jnp.maximum(y, 0.0) if relu else y


def sum_nll(logits, labels):
    """Sum over the batch of -log softmax(logits)[label]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=1))


def sum_loss_and_grads(forward, params, batch):
    """(sum of the items' losses, its gradient, number of items): the
    comparison adds chunks up and divides by the count."""
    image, labels = batch
    f = lambda p: sum_nll(forward(p, image.astype(jnp.float32)), labels)
    loss, grads = jax.value_and_grad(f)(params)
    return loss, grads, labels.shape[0]
