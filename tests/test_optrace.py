"""Operator names in what the program compiles (FFModel._apply's
``jax.named_scope``s) and obs/optrace.py, which reads them back: every
operator of the graph is named in the compiled step's ``op_name``
metadata, each layer's matmul or convolution is charged to that layer's
forward and backward, the optimizer to ``ff_update``, a plan's regrids to
``ff_regrid.<op>.<input>``."""

import re

import numpy as np
import pytest

from flexflow_tpu.obs import optrace
from flexflow_tpu.ops import Flat


def _named(hlo_text, name):
    """Is ``name`` a scope of some instruction's ``op_name``?"""
    return re.search(r'op_name="[^"]*[/(]' + re.escape(name) + r'[)/]',
                     hlo_text) is not None


def _heavy(hlo_text, table):
    """{(operator, pass): [instruction]} over the module's dots and
    convolutions."""
    out = {}
    for line in hlo_text.splitlines():
        m = optrace._INSTRUCTION.match(line)
        if m and (" dot(" in line or " convolution(" in line):
            out.setdefault(table[m.group(1)], []).append(m.group(1))
    return out


@pytest.fixture(scope="module")
def alexnet(machine1):
    import jax

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.alexnet import build_alexnet

    ff = build_alexnet(FFConfig(batch_size=2, input_height=64,
                                input_width=64, num_classes=10), machine1)
    batch = (jax.ShapeDtypeStruct((2, 64, 64, 3), np.float32),
             jax.ShapeDtypeStruct((2,), np.int32))
    return ff, batch, ff.compile_train_step(*batch).as_text()


@pytest.fixture(scope="module")
def tiny_lm(machine1):
    import jax

    from flexflow_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)

    ff = TransformerLM(TransformerConfig(
        batch_size=2, seq_length=16, num_layers=2, d_model=32, num_heads=4,
        d_ff=64, vocab_size=64, causal=True, seed=5), machine1, None)
    tok = jax.ShapeDtypeStruct((2, 16), np.int32)
    return ff, (tok, tok), ff.compile_train_step(tok, tok).as_text()


@pytest.mark.parametrize("which", ["alexnet", "tiny_lm"])
def test_every_operator_is_named_in_the_compiled_step(which, request):
    ff, _, hlo = request.getfixturevalue(which)
    # a Flat is a reshape: it compiles to a bitcast, which carries nothing
    missing = [op.name for op in ff.layers
               if not isinstance(op, Flat) and not _named(hlo, op.name)]
    assert missing == []
    assert _named(hlo, optrace.UPDATE_SCOPE)
    assert optrace.module_name(hlo) == "jit_ff_train_step"


def test_alexnet_layers_land_in_forward_backward_and_update(alexnet):
    ff, batch, hlo = alexnet
    table = optrace.operator_table(hlo, {op.name for op in ff.layers})
    heavy = _heavy(hlo, table)
    convs = ["conv1", "conv2", "conv3", "conv4", "conv5"]
    linears = ["lienar1", "linear2", "linear3"]     # sic: models/alexnet
    for name in convs + linears:
        assert heavy.get((name, "forward")), name
    for name in linears:
        # the data gradient and the weight gradient
        assert len(heavy[(name, "backward")]) == 2, name
    for name in convs[1:]:
        # conv1's input is the image, which has no gradient.  (The CPU
        # compiler rebuilds the weight-gradient convolutions without
        # metadata, so here they read "other"; the TPU's keeps them
        # inside named fusions.)
        assert heavy.get((name, "backward")), name
    updates = [k for k, v in table.items() if v == ("ff_update", "update")]
    assert len(updates) >= 16          # 8 layers x (kernel, bias)
    assert not any(v[1] == "regrid" for v in table.values())
    assert {v[1] for v in table.values()} <= set(optrace.PASSES)
    # the model's own entry point gives the same table
    assert ff.operator_table(*batch) == table


def test_operator_table_without_a_batch_is_of_the_step_that_ran(alexnet):
    import jax.numpy as jnp

    ff, batch, _ = alexnet
    ff._ran_step = None
    with pytest.raises(ValueError, match="no train step has run"):
        ff.operator_table()
    params, state = ff.init(0)
    step = ff.make_train_step()
    ff.make_train_step()        # a step that is built and never runs
    assert ff._ran_step is None
    step(params, state, ff.init_opt_state(params),
         jnp.zeros((2, 64, 64, 3), jnp.float32), jnp.zeros((2,), jnp.int32))
    assert ff._ran_step is step
    *_, image, labels = step.first_call
    assert [(a.shape, a.dtype) for a in (image, labels)] == [
        ((2, 64, 64, 3), np.float32), ((2,), np.int32)]
    assert image.sharding is not None and labels.sharding is not None
    assert ff.operator_table() == ff.operator_table(*batch)


@pytest.mark.parametrize("dropped, match", [
    (None, r"names 0 of 13 operators and not \['conv1'"),
    ("conv3", r"names 11 of 13 operators and not \['conv3'\]"),
])
def test_a_step_from_a_cache_written_without_the_scopes_is_refused(
        alexnet, monkeypatch, dropped, match):
    """JAX leaves metadata out of a compile-cache key, so a shared cache
    directory can serve the same step compiled by a program without (or
    with other) scopes; the table is then refused, not read as 'other':
    every operator but a pure reshape has to be named."""
    ff, batch, hlo = alexnet
    if dropped is None:
        served = re.sub(r', metadata=\{[^}]*\}', "", hlo)
    else:
        served = re.sub(r'([/(])' + dropped + r'([)/])', r"\1renamed\2",
                        hlo)

    class Served:
        def as_text(self):
            return served

    monkeypatch.setattr(ff, "compile_train_step", lambda *b: Served())
    with pytest.raises(ValueError, match=match):
        ff.operator_table(*batch)


def test_tiny_lm_layers_land_in_forward_backward_and_update(tiny_lm):
    ff, _, hlo = tiny_lm
    table = optrace.operator_table(hlo, {op.name for op in ff.layers})
    heavy = _heavy(hlo, table)
    for blk in ("blk0", "blk1"):
        for name in (f"{blk}_attn", f"{blk}_ff1", f"{blk}_ff2"):
            assert heavy.get((name, "forward")), name
            assert len(heavy[(name, "backward")]) \
                >= 2 * len(heavy[(name, "forward")]), name
    assert heavy.get(("lm_head", "forward"))
    assert len(heavy[("lm_head", "backward")]) == 2
    assert ("", "other") not in heavy      # no matmul goes unnamed
    assert any(v == ("ff_update", "update") for v in table.values())
    # the loss runs under its op's name
    assert any(v[0] == "softmax" for v in table.values())


@pytest.mark.parametrize("planner", ["on", "off"])
def test_a_plans_regrids_are_named_on_four_devices(planner):
    """Under a hybrid plan on 4 virtual devices the resharding between
    differently gridded operators carries ``ff_regrid.<op>.<input>``,
    from the planned path and from the legacy per-trace one."""
    import jax

    import __graft_entry__ as ge
    from flexflow_tpu.machine import MachineModel
    from flexflow_tpu.strategy import ParallelConfig, Strategy

    machine = MachineModel(devices=jax.devices()[:4])
    devs = tuple(range(4))
    s = Strategy()
    s["conv1"] = ParallelConfig((2, 2, 1, 1), devs)
    s["conv2"] = ParallelConfig((1, 1, 2, 2), devs)
    s["linear1"] = ParallelConfig((2, 2), devs)
    s["linear2"] = ParallelConfig((4, 1), devs)
    ff, cfg = ge._tiny_model(machine, s)
    cfg.regrid_planner = planner
    from flexflow_tpu.data.synthetic import _batch_sharding

    sh = _batch_sharding(machine)
    batch = (jax.ShapeDtypeStruct((cfg.batch_size, 32, 32, 3), np.float32,
                                  sharding=sh),
             jax.ShapeDtypeStruct((cfg.batch_size,), np.int32, sharding=sh))
    hlo = ff.compile_train_step(*batch).as_text()
    scopes = set(re.findall(r"ff_regrid\.[\w]+\.\d+", hlo))
    assert scopes and scopes <= {f"ff_regrid.{op.name}.{i}"
                                 for op in ff.layers
                                 for i in range(len(op.inputs))}
    table = optrace.operator_table(hlo, {op.name for op in ff.layers})
    regrids = {v[0] for v in table.values() if v[1] == "regrid"}
    assert regrids and regrids <= scopes
    # the operators of the plan are still named beside their regrids
    assert {"conv1", "conv2", "linear1", "linear2"} <= {
        v[0] for v in table.values()}


@pytest.mark.parametrize("op_name,want", [
    ("jit(ff_train_step)/jvp(conv1)/conv_general_dilated",
     ("conv1", "forward")),
    ("jit(ff_train_step)/transpose(jvp(conv1))/conv_general_dilated",
     ("conv1", "backward")),
    ("jit(ff_train_step)/jit(main)/transpose(jvp(blk0_attn))/jvp(ff_flash_bwd)"
     "/pallas_call", ("blk0_attn", "backward")),
    ("jit(ff_train_step)/ff_update/sub", ("ff_update", "update")),
    ("jit(ff_train_step)/jvp(ff_regrid.linear1.0)/sharding_constraint",
     ("ff_regrid.linear1.0", "regrid")),
    ("jit(ff_train_step)/transpose(jvp(ff_regrid.linear1.0))/all-to-all",
     ("ff_regrid.linear1.0", "regrid")),
    ("jit(ff_train_step)/jvp()/reduce_sum", ("", "other")),
    ("jit(ff_train_step)/convert_element_type", ("", "other")),
    ("jit(predict_step)/conv1/conv_general_dilated", ("conv1", "forward")),
    ("jit(ff_train_step)/jvp(lm_head)/softmax/dot_general",
     ("lm_head", "forward")),
])
def test_classify(op_name, want):
    ops = {"conv1", "blk0_attn", "lm_head", "softmax", "linear1"}
    assert optrace.classify(op_name, ops) == want
    if want[1] != "other":
        # without the operators' names the outermost scope is taken
        assert optrace.classify(op_name) == want


def test_classify_skips_scopes_that_are_not_operators():
    path = "jit(ff_train_step)/jvp(helper)/jvp(conv1)/mul"
    assert optrace.classify(path, {"conv1"}) == ("conv1", "forward")
    assert optrace.classify(path) == ("helper", "forward")


def test_a_fusion_without_metadata_takes_what_it_calls():
    hlo = '''HloModule jit_ff_train_step, is_scheduled=true

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %a = f32[4]{0} add(%p, %p), metadata={op_name="jit(ff_train_step)/jvp(fc)/add"}
  ROOT %m = f32[4]{0} multiply(%a, %a), metadata={op_name="jit(ff_train_step)/jvp(fc)/mul"}
}

%fused_computation.1 (p.1: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  ROOT %n = f32[4]{0} negate(%p.1)
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation
  %fusion.1 = f32[4]{0} fusion(%fusion), kind=kLoop, calls=%fused_computation.1
  ROOT %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(ff_train_step)/ff_update/sub"}
}
'''
    table = optrace.operator_table(hlo)
    assert table["fusion"] == ("fc", "forward")         # by its body
    assert table["fusion.1"] == ("", "other")           # nothing to go by
    assert table["fusion.2"] == ("ff_update", "update")  # its own wins
    assert table["x"] == ("", "other")
    assert optrace.module_name(hlo) == "jit_ff_train_step"
