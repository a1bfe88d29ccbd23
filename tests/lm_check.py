"""The whole-model check the token models' tests share: a model's loss and
every operator's gradient against its plain reference in
``benchmarks/reference``, each side compiled as one program.

A whole model run eagerly compiles and dispatches each primitive of its
recomputed, kernel-bearing graph one at a time, and takes several times
as long as the same check compiled; a test that runs a whole model runs
it under ``jax.jit``, as this does."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np


def check_against_reference(ff, cfg, reference, b, s,
                            kernels=contextlib.nullcontext):
    """``ff``'s loss and gradients on ``b`` x ``s`` seeded tokens, traced
    and run inside ``kernels()``, against ``reference.sum_loss_and_grads``
    at the highest matmul precision: the loss to a relative 1e-5, every
    leaf of every operator to a relative 2e-3 above a floor of 2e-4 of
    the leaf's largest.  The weights are seeded and every one is moved
    off its initial value (gains off one, biases off zero).  Returns the
    operators' parameters as the reference read them."""
    from benchmarks import harness

    params, state = ff.init(4)
    params = jax.tree.map(lambda a: a + 0.1 * jnp.asarray(
        np.random.RandomState(a.size % 97).randn(*a.shape), jnp.float32),
        params)
    toks = jax.random.randint(jax.random.PRNGKey(5), (b, s), 0,
                              cfg["vocab_size"])
    with kernels():
        (loss, _), grads = jax.jit(jax.value_and_grad(
            lambda p: ff.loss_fn(p, state, toks, toks), has_aux=True))(
                params)
    plain = harness.op_params(ff, params)
    with jax.default_matmul_precision("highest"):
        total, want, n = jax.jit(
            lambda p, t: reference.sum_loss_and_grads(p, (t, t), cfg))(
                plain, toks)
    n = int(n)
    assert n == b * (s - 1)
    np.testing.assert_allclose(loss, total / n, rtol=1e-5)
    assert set(want) == set(grads)
    for op, leaves in want.items():
        for leaf, g in leaves.items():
            scale = float(jnp.max(jnp.abs(g))) / n
            np.testing.assert_allclose(
                grads[op][leaf], g / n, rtol=2e-3, atol=2e-4 * scale + 1e-9,
                err_msg=f"{op}.{leaf}")
    return plain
