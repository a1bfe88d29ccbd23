"""Builds a CNN configuration (``"builder": "cnn"``) through the repo's
normal path: ``FFConfig`` (with the plan's strategy file, vetted by the
plan checker as ``apps/cnn.py`` does), the model builder the
configuration names, the donated train step, and one fixed batch made on
the device from the seed."""

from __future__ import annotations

import dataclasses
from typing import Dict

from benchmarks import harness


def _model_builder(name: str):
    from flexflow_tpu import models as zoo

    return {"inception_v3": zoo.build_inception_v3,
            "alexnet": zoo.build_alexnet}[name]


def build_train(config: Dict, mix: Dict, devices, seed: int,
                strategy_file: str = "") -> Dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.traffic_gen import fold_seed
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.data.synthetic import _batch_sharding
    from flexflow_tpu.machine import MachineModel

    machine = MachineModel(devices)
    opt = config["optimizer"]
    batch = int(mix["batch"])
    size = int(config["image_size"])
    cfg = FFConfig(batch_size=batch, input_height=size, input_width=size,
                   num_classes=int(config["num_classes"]),
                   compute_dtype=config["compute_dtype"],
                   param_dtype=config["param_dtype"],
                   learning_rate=float(opt["learning_rate"]),
                   weight_decay=float(opt["weight_decay"]),
                   momentum=float(opt["momentum"]),
                   print_freq=0, seed=fold_seed(seed, 0),
                   strategy_file=strategy_file)
    build = _model_builder(config["model"])
    if cfg.strategies:
        from flexflow_tpu.strategy import Strategy
        from flexflow_tpu.verify.plan import check_plan

        shadow = dataclasses.replace(cfg, strategies=Strategy(),
                                     strategy_file="")
        check_plan(build(shadow, machine), cfg.strategies, machine,
                   label=strategy_file)
    ff = build(cfg, machine)
    sharding = _batch_sharding(machine)

    @jax.jit
    def make_batch(seed32):
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed32))
        # drawn flat: a minor dimension of 3 is padded to 128 lanes on
        # the TPU, and the generator would fill the padding too
        img = jax.random.normal(k1, (batch, size * size * 3),
                                jnp.float32).reshape(batch, size, size, 3)
        lbl = jax.random.randint(k2, (batch,), 0, cfg.num_classes,
                                 jnp.int32)
        return (jax.lax.with_sharding_constraint(img, sharding),
                jax.lax.with_sharding_constraint(lbl, sharding))

    return {"model": ff, "machine": machine, "make_batch": make_batch,
            "items_per_step": batch,
            "op_params": lambda params: harness.op_params(ff, params)}

