"""Builds the transformer configurations (``"builder":
"transformer_lm"``) through ``models/transformer.py`` ``TransformerLM``,
which ``apps/lm.py`` trains.  The
configuration file keeps the public ``config.json``'s key names."""

from __future__ import annotations

from typing import Dict

from benchmarks import harness


def _t_config(config: Dict, batch: int, seq: int, seed: int, dtype: str):
    from benchmarks.traffic_gen import fold_seed
    from flexflow_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        batch_size=batch, seq_length=seq,
        num_layers=int(config["n_layer"]), d_model=int(config["n_embd"]),
        num_heads=int(config["n_head"]),
        d_ff=int(config.get("n_inner") or 4 * int(config["n_embd"])),
        vocab_size=int(config["vocab_size"]), causal=True,
        learning_rate=float(config["optimizer"]["learning_rate"]),
        compute_dtype=dtype, param_dtype=config["param_dtype"],
        seed=fold_seed(seed, 0))


def build_train(config: Dict, mix: Dict, devices, seed: int,
                strategy_file: str = "") -> Dict:
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.data.synthetic import _batch_sharding
    from flexflow_tpu.machine import MachineModel
    from flexflow_tpu.models.transformer import TransformerLM

    if strategy_file:
        raise SystemExit("benchmark: transformer_lm cells take no plan yet")
    machine = MachineModel(devices)
    batch, seq = int(mix["batch"]), int(mix["seq_length"])
    if seq > int(config["n_positions"]):
        raise SystemExit(f"benchmark: {seq} positions, the configuration "
                         f"has {config['n_positions']}")
    ff = TransformerLM(_t_config(config, batch, seq, seed,
                                 config["compute_dtype"]), machine)
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
