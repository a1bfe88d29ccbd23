"""End-to-end FFModel tests: AlexNet on the 8-device CPU mesh, pure DP and
hybrid strategies, and the key FlexFlow invariant — identical loss
trajectories under any strategy (SURVEY.md §4)."""

import numpy as np


from flexflow_tpu.config import FFConfig
from flexflow_tpu.data import synthetic_batches
from flexflow_tpu.models.alexnet import build_alexnet
from flexflow_tpu.strategy import ParallelConfig, Strategy


def small_config(**kw):
    cfg = FFConfig(batch_size=8, input_height=32, input_width=32,
                   num_iterations=3, print_freq=0, num_classes=10, seed=7)
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def tiny_model(ff_config, machine):
    """A small conv->pool->flat->linear->softmax net for fast tests."""
    from flexflow_tpu.model import FFModel

    ff = FFModel(ff_config, machine)
    img = ff.create_input((ff_config.batch_size, 16, 16, 3), name="image")
    t = ff.conv2d("conv1", img, 8, 3, 3, 1, 1, 1, 1, relu=True)
    t = ff.pool2d("pool1", t, 2, 2, 2, 2, 0, 0)
    t = ff.conv2d("conv2", t, 16, 3, 3, 2, 2, 1, 1, relu=True)
    t = ff.flat("flat", t)
    t = ff.linear("linear1", t, 32)
    t = ff.linear("linear2", t, 10, relu=False)
    t = ff.softmax("softmax", t)
    return ff


def run_losses(machine, strategies=None, iters=4, seed=7):
    cfg = small_config()
    if strategies:
        cfg.strategies = strategies
    ff = tiny_model(cfg, machine)
    params, state = ff.init(seed)
    opt_state = ff.init_opt_state(params)
    step = ff.make_train_step()
    data = synthetic_batches(machine, cfg.batch_size, 16, 16,
                             num_classes=10, mode="random", seed=13)
    losses = []
    for _ in range(iters):
        img, lbl = next(data)
        params, state, opt_state, loss = step(params, state, opt_state,
                                              img, lbl)
        losses.append(float(loss))
    return losses


def test_tiny_model_trains(machine8):
    losses = run_losses(machine8)
    assert len(losses) == 4
    assert losses[-1] < losses[0], f"loss did not decrease: {losses}"
    assert np.isfinite(losses).all()


def test_strategy_invariance_dp_vs_hybrid(machine8):
    """THE FlexFlow correctness property: any strategy gives the same loss
    trajectory (reference achieves this by construction via Legion; we must
    prove GSPMD sharding preserves it)."""
    dp = run_losses(machine8, strategies=None)

    hybrid = Strategy()
    # conv1: spatial (h x w) partitioning; conv2: channel x batch
    hybrid["conv1"] = ParallelConfig((2, 2, 1, 2), tuple(range(8)))
    hybrid["conv2"] = ParallelConfig((1, 1, 4, 2), tuple(range(8)))
    # linear1: tensor-parallel over output channels + batch
    hybrid["linear1"] = ParallelConfig((4, 2), tuple(range(8)))
    hybrid["linear2"] = ParallelConfig((2, 4), tuple(range(8)))
    hy = run_losses(machine8, strategies=hybrid)

    np.testing.assert_allclose(dp, hy, rtol=2e-4, atol=2e-5)


def test_strategy_invariance_device_subset(machine8):
    """Ops restricted to a subset of devices (operator parallelism) still
    produce the same numbers."""
    dp = run_losses(machine8, strategies=None)
    sub = Strategy()
    sub["conv1"] = ParallelConfig((1, 1, 1, 4), (0, 1, 2, 3))
    sub["linear1"] = ParallelConfig((2, 2), (4, 5, 6, 7))
    got = run_losses(machine8, strategies=sub)
    np.testing.assert_allclose(dp, got, rtol=2e-4, atol=2e-5)


def test_alexnet_builds_and_steps(machine8):
    cfg = small_config(batch_size=8, input_height=64, input_width=64)
    ff = build_alexnet(cfg, machine8)
    assert len(ff.layers) == 13
    names = [op.name for op in ff.layers]
    assert names[:3] == ["conv1", "pool1", "conv2"]
    params, state = ff.init()
    opt = ff.init_opt_state(params)
    step = ff.make_train_step()
    data = synthetic_batches(machine8, cfg.batch_size, 64, 64, mode="random",
                             seed=3)
    img, lbl = next(data)
    params, state, opt, loss = step(params, state, opt, img, lbl)
    assert np.isfinite(float(loss))


def test_fit_reports_throughput(machine8):
    cfg = small_config()
    ff = tiny_model(cfg, machine8)
    data = synthetic_batches(machine8, cfg.batch_size, 16, 16,
                             num_classes=10, mode="random")
    out = ff.fit(data, num_iterations=3, warmup=1, log=lambda *a: None)
    assert out["images_per_sec"] > 0
    assert len(out["loss"]) == 3


def test_eval_step(machine8):
    cfg = small_config()
    ff = tiny_model(cfg, machine8)
    params, state = ff.init()
    ev = ff.make_eval_step()
    data = synthetic_batches(machine8, cfg.batch_size, 16, 16,
                             num_classes=10, mode="random")
    img, lbl = next(data)
    loss, acc = ev(params, state, img, lbl)
    assert np.isfinite(float(loss))
    assert 0.0 <= float(acc) <= 1.0


def test_compute_dtype_reaches_token_models(machine8):
    """--dtype must propagate from the embedding through the whole seq
    stack (regression: it used to stop at the f32 embed output)."""
    from flexflow_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)

    tcfg = TransformerConfig(batch_size=8, seq_length=16, num_layers=1,
                             d_model=16, num_heads=4, d_ff=32,
                             vocab_size=64, causal=True,
                             compute_dtype="bfloat16")
    tlm = TransformerLM(tcfg, machine8)
    params, state = tlm.init(seed=0)
    import jax
    import jax.numpy as jnp
    toks = jnp.zeros((8, 16), "int32")
    values, _ = jax.jit(lambda p: tlm.apply(
        p, state, {tlm.tokens.tid: toks, tlm.labels.tid: toks},
        train=True))(params)
    embed_out = values[tlm.layers[0].output.tid]
    assert embed_out.dtype == jnp.bfloat16
    # master params stay f32 (bf16 is compute-only)
    assert params["embed"]["table"].dtype == jnp.float32
