"""Transformer family + ring attention tests: numerics vs dense reference,
context-parallel invariance, training, and SOAP search over the new ops."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu.models.transformer import TransformerConfig, TransformerLM
from flexflow_tpu.parallel.ring_attention import (blockwise_attention,
                                                  ring_attention)
from flexflow_tpu.strategy import ParallelConfig, Strategy


def dense_attn(q, k, v, causal):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if causal:
        S = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((S, S))) == 1, s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_dense(causal):
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(2, 3, 16, 8), jnp.float32)
               for _ in range(3))
    ref = dense_attn(q, k, v, causal)
    got = blockwise_attention(q, k, v, causal, block_size=4)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_dense(machine8, causal):
    from jax.sharding import Mesh

    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(2, 4, 32, 8), jnp.float32)
               for _ in range(3))
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("n", "s"))
    ref = dense_attn(q, k, v, causal)
    got = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh, "s",
                                                 causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # gradient parity
    g_ref = jax.jit(jax.grad(lambda q: dense_attn(q, k, v, causal).sum()))(q)
    g_ring = jax.jit(jax.grad(
        lambda q: ring_attention(q, k, v, mesh, "s", causal).sum()))(q)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-5)


def tiny_transformer(machine, strategies=None, causal=False):
    cfg = TransformerConfig(batch_size=8, seq_length=16, num_layers=2,
                            d_model=32, num_heads=4, d_ff=64,
                            vocab_size=64, causal=causal,
                            learning_rate=1e-2, seed=5)
    return TransformerLM(cfg, machine, strategies)


def tokens_for(machine, b=8, s=16, vocab=64, seed=7):
    from jax.sharding import PartitionSpec as P

    rng = np.random.RandomState(seed)
    n = machine.num_devices
    sh = machine.sharding(ParallelConfig((n,), tuple(range(n))), ("n",),
                          P("n"))
    toks = rng.randint(0, vocab, (b, s)).astype("int32")
    return jax.device_put(toks, sh)


def test_transformer_trains(machine8):
    m = tiny_transformer(machine8)
    params, state = m.init()
    step = m.make_train_step()
    toks = tokens_for(machine8)
    losses = []
    for _ in range(6):
        params, state, _, loss = step(params, state, None, toks, toks)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert abs(losses[0] - np.log(64)) < 1.0
    assert losses[-1] < losses[0] - 0.1, losses


def test_transformer_sop_invariance(machine8):
    """Loss trajectory invariant under a full SOAP strategy: ring-attention
    sequence parallelism + head TP + DP, TP MLPs, sequence-sharded norms."""
    def run(strategies):
        m = tiny_transformer(machine8, strategies)
        params, state = m.init()
        step = m.make_train_step()
        toks = tokens_for(machine8)
        out = []
        for _ in range(3):
            params, state, _, loss = step(params, state, None, toks, toks)
            out.append(float(loss))
        return out

    base = run(None)

    s = Strategy()
    devs = tuple(range(8))
    s["blk0_attn"] = ParallelConfig((4, 1, 2), devs)   # ring CP x DP
    s["blk1_attn"] = ParallelConfig((1, 4, 2), devs)   # head TP x DP
    s["blk0_ff1"] = ParallelConfig((4, 2), devs)       # channel TP
    s["blk0_ff2"] = ParallelConfig((2, 4), devs)
    s["blk1_ln1"] = ParallelConfig((4, 2), devs)       # seq-sharded norm
    s["lm_head"] = ParallelConfig((8, 1), devs)        # vocab TP
    got = run(s)
    np.testing.assert_allclose(base, got, rtol=3e-4, atol=3e-5)


def test_gpt_causal_masks_future(machine8):
    """In a causal model, changing future tokens must not change current
    logits."""
    m = tiny_transformer(machine8, causal=True)
    params, state = m.init()
    toks = np.asarray(tokens_for(machine8))
    t1 = jnp.asarray(toks)
    t2 = jnp.asarray(np.concatenate([toks[:, :8],
                                     (toks[:, 8:] + 1) % 64], axis=1))

    def logits(tk):
        inputs = {m.tokens.tid: tk, m.labels.tid: tk}
        values, _ = m.apply(params, state, inputs, train=False)
        lm_head = [op for op in m.layers if op.name == "lm_head"][0]
        return values[lm_head.output.tid]

    l1, l2 = logits(t1), logits(t2)
    np.testing.assert_allclose(np.asarray(l1[:, :8]), np.asarray(l2[:, :8]),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(l1[:, 8:] - l2[:, 8:]).max()) > 1e-3


def test_transformer_search(machine8):
    """SOAP search over the transformer op set produces an executable
    strategy at least as good as DP."""
    from flexflow_tpu.sim import StrategySearch

    m = tiny_transformer(machine8)
    search = StrategySearch(m, machine8)
    dp_time = search.simulate(search.dp_assignment())
    strategy, info = search.search(iters=2000, seed=3)
    assert info["best_time"] <= dp_time + 1e-12
    m2 = tiny_transformer(machine8, strategy)
    params, state = m2.init()
    step = m2.make_train_step()
    toks = tokens_for(machine8)
    _, _, _, loss = step(params, state, None, toks, toks)
    assert np.isfinite(float(loss))
