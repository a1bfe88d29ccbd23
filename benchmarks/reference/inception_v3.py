"""Inception-v3 (Szegedy et al. 2015, arXiv:1512.00567) as the FlexFlow
reference's ``inception.h`` lays it out: 299x299 input, the five-conv
stem, 3 x A, B, 4 x C, D, 2 x E, 8x8 average pool, 1000-way linear,
softmax cross-entropy, mean over the batch.

Departures from the paper, which the configuration file lists under
``assumed``: every convolution is conv + bias + ReLU with no batch
normalisation, there is no auxiliary classifier and no dropout (all as in
``inception.h``); an average pool divides by the number of positions
inside the image; max pools are followed by a ReLU (no effect after a
ReLU convolution).
"""

import jax.numpy as jnp

from benchmarks.reference import cnn_layers as L


def _c(p, name, x, stride=(1, 1), pad=(0, 0)):
    return L.conv(p[name], x, stride, pad, relu=True)


def _avg3(x):
    return L.avg_pool(x, (3, 3), (1, 1), (1, 1), relu=True)


def _a(p, n, x):
    t1 = _c(p, f"{n}_b1_1x1", x)
    t2 = _c(p, f"{n}_b2_5x5", _c(p, f"{n}_b2_1x1", x), pad=(2, 2))
    t3 = _c(p, f"{n}_b3_1x1", x)
    t3 = _c(p, f"{n}_b3_3x3a", t3, pad=(1, 1))
    t3 = _c(p, f"{n}_b3_3x3b", t3, pad=(1, 1))
    t4 = _c(p, f"{n}_b4_1x1", _avg3(x))
    return jnp.concatenate([t1, t2, t3, t4], axis=3)


def _b(p, n, x):
    t1 = _c(p, f"{n}_b1_3x3", x, (2, 2))
    t2 = _c(p, f"{n}_b2_1x1", x)
    t2 = _c(p, f"{n}_b2_3x3a", t2, pad=(1, 1))
    t2 = _c(p, f"{n}_b2_3x3b", t2, (2, 2))
    t3 = L.max_pool(x, (3, 3), (2, 2), relu=True)
    return jnp.concatenate([t1, t2, t3], axis=3)


def _cc(p, n, x):
    t1 = _c(p, f"{n}_b1_1x1", x)
    t2 = _c(p, f"{n}_b2_1x1", x)
    t2 = _c(p, f"{n}_b2_1x7", t2, pad=(0, 3))
    t2 = _c(p, f"{n}_b2_7x1", t2, pad=(3, 0))
    t3 = _c(p, f"{n}_b3_1x1", x)
    t3 = _c(p, f"{n}_b3_7x1a", t3, pad=(3, 0))
    t3 = _c(p, f"{n}_b3_1x7a", t3, pad=(0, 3))
    t3 = _c(p, f"{n}_b3_7x1b", t3, pad=(3, 0))
    t3 = _c(p, f"{n}_b3_1x7b", t3, pad=(0, 3))
    t4 = _c(p, f"{n}_b4_1x1", _avg3(x))
    return jnp.concatenate([t1, t2, t3, t4], axis=3)


def _d(p, n, x):
    t1 = _c(p, f"{n}_b1_3x3", _c(p, f"{n}_b1_1x1", x), (2, 2))
    t2 = _c(p, f"{n}_b2_1x1", x)
    t2 = _c(p, f"{n}_b2_1x7", t2, pad=(0, 3))
    t2 = _c(p, f"{n}_b2_7x1", t2, pad=(3, 0))
    t2 = _c(p, f"{n}_b2_3x3", t2, (2, 2))
    t3 = L.max_pool(x, (3, 3), (2, 2), relu=True)
    return jnp.concatenate([t1, t2, t3], axis=3)


def _e(p, n, x):
    t1 = _c(p, f"{n}_b1_1x1", x)
    t2i = _c(p, f"{n}_b2_1x1", x)
    t2 = _c(p, f"{n}_b2_1x3", t2i, pad=(0, 1))
    t3 = _c(p, f"{n}_b2_3x1", t2i, pad=(1, 0))
    t3i = _c(p, f"{n}_b3_3x3", _c(p, f"{n}_b3_1x1", x), pad=(1, 1))
    t4 = _c(p, f"{n}_b3_1x3", t3i, pad=(0, 1))
    t5 = _c(p, f"{n}_b3_3x1", t3i, pad=(1, 0))
    t6 = _c(p, f"{n}_b4_1x1", _avg3(x))
    return jnp.concatenate([t1, t2, t3, t4, t5, t6], axis=3)


def forward(p, x):
    x = _c(p, "conv1", x, (2, 2))
    x = _c(p, "conv2", x)
    x = _c(p, "conv3", x, pad=(1, 1))
    x = L.max_pool(x, (3, 3), (2, 2), relu=True)
    x = _c(p, "conv4", x)
    x = _c(p, "conv5", x, pad=(1, 1))
    x = L.max_pool(x, (3, 3), (2, 2), relu=True)
    for n in ("incA1", "incA2", "incA3"):
        x = _a(p, n, x)
    x = _b(p, "incB1", x)
    for n in ("incC1", "incC2", "incC3", "incC4"):
        x = _cc(p, n, x)
    x = _d(p, "incD1", x)
    for n in ("incE1", "incE2"):
        x = _e(p, n, x)
    x = L.avg_pool(x, x.shape[1:3], (1, 1))
    return L.linear(p["linear1"], x.reshape(x.shape[0], -1))


def sum_loss_and_grads(params, batch, config):
    return L.sum_loss_and_grads(forward, params, batch)
