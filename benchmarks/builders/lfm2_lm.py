"""Builds the short-convolution expert-layer configurations (``"builder":
"lfm2_lm"``) through ``models/lfm2.py`` ``Lfm2LM``, the class
``apps/lm.py --model-config`` trains, with ``FFModel.init``,
``make_train_step`` and ``DevicePrefetcher`` as the other token cells
have them.  The configuration file keeps the public ``config.json``'s key
names; token ids are uniform over the rows of the vocabulary held here."""

from __future__ import annotations

from typing import Dict

from benchmarks import harness


def build_train(config: Dict, mix: Dict, devices, seed: int,
                strategy_file: str = "") -> Dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.traffic_gen import fold_seed
    from flexflow_tpu.data.synthetic import _batch_sharding
    from flexflow_tpu.machine import MachineModel
    from flexflow_tpu.models.lfm2 import Lfm2Config, Lfm2LM

    if strategy_file:
        raise SystemExit("benchmark: lfm2_lm cells take no plan yet")
    machine = MachineModel(devices)
    batch, seq = int(mix["batch"]), int(mix["seq_length"])
    if seq > int(config["max_position_embeddings"]):
        raise SystemExit(f"benchmark: {seq} positions, the configuration "
                         f"has {config['max_position_embeddings']}")
    ff = Lfm2LM(Lfm2Config.from_config(
        config, batch_size=batch, seq_length=seq, seed=fold_seed(seed, 0)),
        machine)
    sharding = _batch_sharding(machine)
    vocab = int(config["vocab_size"])

    @jax.jit
    def make_batch(seed32):
        toks = jax.random.randint(jax.random.PRNGKey(seed32),
                                  (batch, seq), 0, vocab, jnp.int32)
        toks = jax.lax.with_sharding_constraint(toks, sharding)
        return toks, toks   # labels are the tokens; loss_fn shifts them

    return {"model": ff, "machine": machine, "make_batch": make_batch,
            "items_per_step": batch * seq,
            "op_params": lambda params: harness.op_params(ff, params)}
