"""The train step's programs held to what they were: the whole float32
step of the CNN cells, GPT-2's and SmallThinker's traced at the cell's
shape (the other four token cells are held in
``tests/test_smallthinker.py``), and the two master-weight steps'
parameters and optimizer state after three steps, bit for bit."""

import hashlib
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(name, mix):
    from benchmarks import harness

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    builder = harness.load_by_name(os.path.join(ROOT, "benchmarks",
                                                "builders"),
                                   config["builder"])
    return builder.build_train(config, mix, jax.devices()[:1], 1)["model"]


def _digest(text):
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


#: sha256 (first 16) of each cell's whole train step, traced at the
#: cell's shape on one device (the token cells with the kernel gate
#: open), addresses of Python objects left out, as commit 0ecb12e (the
#: four step factories, before they became one over an update rule)
#: traced it
_PARENTS = {"inception_v3": "704a0ce5772270ee",
            "alexnet_owt": "23c0d7a1325f3885",
            "gpt2_small": "95b9d42291240011",
            "smallthinker_21b_a3b": "25dbe21a029b6174"}

_MIXES = {"inception_v3": {"batch": 256},
          "alexnet_owt": {"batch": 2048},
          "gpt2_small": {"batch": 16, "seq_length": 1024},
          "smallthinker_21b_a3b": {"batch": 1, "seq_length": 16384}}


def _traced(name, pallas_kernels, monkeypatch):
    mix = _MIXES[name]
    if "seq_length" not in mix:
        ff = _build(name, mix)
        params, state, opt = ff.abstract_train_state()
        size = ff.config.input_height
        batch = (jax.ShapeDtypeStruct((mix["batch"], size, size, 3),
                                      jnp.float32),
                 jax.ShapeDtypeStruct((mix["batch"],), jnp.int32))
        return str(jax.make_jaxpr(ff.make_train_step())(
            params, state, opt, *batch))
    for kernel in ("flash_attention", "grouped_mm", "fused_ce", "rope",
                   "ssd_scan"):
        mod = importlib.import_module(f"flexflow_tpu.ops.pallas.{kernel}")
        monkeypatch.setattr(mod, "_should_interpret", lambda: False)
    with pallas_kernels():
        ff = _build(name, mix)
        params, state, opt = ff.abstract_train_state()
        toks = jax.ShapeDtypeStruct((mix["batch"], mix["seq_length"]),
                                    jnp.int32)
        return str(jax.make_jaxpr(ff.make_train_step())(
            params, state, opt, toks, toks))


@pytest.mark.parametrize("name", sorted(_PARENTS))
def test_the_cells_train_steps_trace_to_the_parents(name, pallas_kernels,
                                                    monkeypatch):
    assert _digest(_traced(name, pallas_kernels, monkeypatch)) \
        == _PARENTS[name]


# ---------------------------------------------------------------------------
# the master-weight steps


def _tree_digest(tree):
    h = hashlib.sha256()
    for path, leaf in sorted(jax.tree_util.tree_leaves_with_path(tree),
                             key=lambda t: jax.tree_util.keystr(t[0])):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


def _momentum_run():
    """A conv, batch norm and dense CNN stored in bfloat16 with float32
    masters, momentum 0.9 and weight decay: three steps."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.machine import MachineModel
    from flexflow_tpu.model import FFModel

    cfg = FFConfig(batch_size=4, input_height=8, input_width=8,
                   num_classes=8, seed=7, param_dtype="bfloat16",
                   momentum=0.9, weight_decay=1e-3, learning_rate=0.05,
                   print_freq=0)
    ff = FFModel(cfg, MachineModel(jax.devices()[:1]))
    img = ff.create_input((4, 8, 8, 3), name="image")
    t = ff.conv2d("conv1", img, 8, 3, 3, 1, 1, 1, 1, relu=True)
    t = ff.batch_norm("bn1", t, relu=True)
    t = ff.flat("flat", t)
    t = ff.linear("fc", t, 8, relu=False)
    ff.softmax("softmax", t)
    params, state = ff.init()
    opt = ff.init_opt_state(params)
    rng = np.random.RandomState(3)
    image = jnp.asarray(rng.randn(4, 8, 8, 3), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 8, (4,)), jnp.int32)
    step = ff.make_train_step()
    for _ in range(3):
        params, state, opt, _ = step(params, state, opt, image, labels)
    return params, opt


def _sgd_run():
    """A two-layer transformer stored in bfloat16 with float32 masters
    under plain SGD: three steps."""
    from flexflow_tpu.machine import MachineModel
    from flexflow_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)

    cfg = TransformerConfig(batch_size=2, seq_length=8, num_layers=2,
                            d_model=32, num_heads=4, d_ff=64,
                            vocab_size=64, causal=True, learning_rate=0.05,
                            seed=5, param_dtype="bfloat16")
    ff = TransformerLM(cfg, MachineModel(jax.devices()[:1]))
    params, state = ff.init()
    opt = ff.init_opt_state(params)
    toks = jnp.asarray(np.random.RandomState(7).randint(0, 64, (2, 8)),
                       jnp.int32)
    step = ff.make_train_step()
    for _ in range(3):
        params, state, opt, _ = step(params, state, opt, toks, toks)
    return params, opt


#: sha256 (first 16) of the parameters and the optimizer state, leaf by
#: leaf with its path and dtype, after three steps, as commit 0ecb12e
#: computed them on the CPU
_MASTER_PARENTS = {"momentum": ("f108c53b5b676332", "48c44c3af651abd4"),
                   "sgd": ("d4b5ed53017b8908", "4b433d8e35a61ebb")}


@pytest.mark.parametrize("rule", sorted(_MASTER_PARENTS))
def test_master_weight_steps_land_where_the_parents_did(rule):
    params, opt = {"momentum": _momentum_run, "sgd": _sgd_run}[rule]()
    masters = [k for sub in opt.values() for k in sub
               if k.endswith("__master")]
    assert masters and all(
        str(v.dtype) == "bfloat16" for sub in params.values()
        for v in sub.values() if jnp.issubdtype(v.dtype, jnp.floating))
    assert (_tree_digest(params), _tree_digest(opt)) \
        == _MASTER_PARENTS[rule]
