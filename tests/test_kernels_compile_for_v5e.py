"""The fused head's kernels, the windowed flash kernels, the state-space
scan's kernels, the rotary kernels (alone and inside
``GroupedQueryAttention``), the held experts' grouped products and one
cell's whole train step compiled for a described TPU v5e, at
the widths the benchmark's cells run, at the widest the fusion takes and
at the corners of the scan's and the rotary rule: interpret mode says nothing about
what the chip's compiler accepts (VMEM above all), and a compile here
costs no chip time.  Nothing runs: a pass is not a measurement."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("d,v,dtype", [
    (768, 50257, "bfloat16"),      # gpt2_small
    (2048, 100352, "bfloat16"),    # granite_4_0_h_micro (moonlight: 20480)
    (4096, 32000, "bfloat16"),     # the widest head _fusion_ok lets in
    (2048, 32000, "float32"),
])
def test_fused_head_compiles_at_the_tiles_the_shapes_pick(one_chip, d, v,
                                                          dtype):
    from flexflow_tpu.ops.pallas import fused_ce as ce
    n = 16384

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    def step(x, w, b, labels):
        loss = lambda x, w, b: ce.fused_linear_ce(
            x, w, b, labels, interpret=False).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(x, w, b)

    text = jax.jit(step).lower(
        shape((n, d), dtype), shape((d, v), jnp.float32),
        shape((v,), jnp.float32), shape((n,), jnp.int32)
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2, \
        "forward and one backward kernel"
    logits = [m for m in re.findall(r"\w+\[(\d+),(\d+)\]", text)
              if int(m[0]) >= n and int(m[1]) >= v]
    assert not logits, "an array of tokens x vocabulary reached HBM"


@pytest.mark.parametrize("heads,window,names", [
    # laguna_s_2_1's sliding layers: blocks of 512, the band of two
    (72, 512, ("ff_flash_win_fwd", "ff_flash_win_bwd")),
    # a window of several blocks (1024 x 1024 tiles, a band of five)
    (8, 4096, ("ff_flash_win_fwd", "ff_flash_win_bwd")),
    # and its full layers: the causal kernels under their own names
    (48, None, ("ff_flash_fwd", "ff_flash_bwd")),
])
def test_flash_compiles_at_the_laguna_cells_shapes(one_chip, heads, window,
                                                   names):
    import importlib

    fa = importlib.import_module("flexflow_tpu.ops.pallas.flash_attention")
    x = jax.ShapeDtypeStruct((2, 8192, heads * 128), jnp.bfloat16,
                             sharding=one_chip)

    def step(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: fa.flash_attention_packed(
                q, k, v, heads, True, interpret=False, window=window
            ).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)

    text = jax.jit(step).lower(x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for name in names:
        assert re.search(rf"\b{name}\b", text), name
    assert ("ff_flash_win_" in text) == (window is not None)


@pytest.mark.parametrize("heads,head_dim,state,chunk,dtype", [
    (64, 64, 128, 256, "bfloat16"),    # granite_4_0_h_micro
    # the corners of ``ssd_scan.fits`` (compiled, not timed)
    (64, 64, 128, 256, "float32"),     # the most VMEM the rule takes
    (8, 32, 128, 128, "float32"),      # the shorter chunk, one group
    (16, 16, 128, 128, "bfloat16"),    # the narrowest head, two groups
    (256, 16, 128, 256, "bfloat16"),   # the most groups of heads
])
def test_scan_compiles_and_no_chunk_matrix_reaches_hbm(one_chip, heads,
                                                       head_dim, state,
                                                       chunk, dtype):
    from flexflow_tpu.ops.pallas import ssd_scan as ss

    b, s = 2, 8 * chunk
    assert ss.fits(chunk, heads, head_dim, state, dtype)

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    def step(xbc, dt, a, d):
        return jax.value_and_grad(
            lambda *args: ss.ssd_scan(
                *args, heads=heads, head_dim=head_dim, state=state,
                chunk=chunk, interpret=False).astype(jnp.float32).sum(),
            (0, 1, 2, 3))(xbc, dt, a, d)

    text = jax.jit(step).lower(
        shape((b, s, heads * head_dim + 2 * state), dtype),
        shape((b, s, heads), jnp.float32), shape((heads,), jnp.float32),
        shape((heads,), jnp.float32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for name in ("ff_ssd_fwd", "ff_ssd_bwd"):
        assert re.search(rf"\b{name}\b", text), name
    # a decay matrix or m would be (.., heads, chunk, chunk)
    assert not re.search(rf"\[[\d,]*{chunk},{chunk}\]", text)


_YARN_64 = {"dim": 64, "rope_theta": 500000.0, "rope_type": "yarn",
            "factor": 32.0, "original_max_position_embeddings": 4096,
            "beta_fast": 32.0, "beta_slow": 1.0}


def _entry(text):
    """name -> (opcode, operand names, line) of the entry computation's
    instructions, in schedule order."""
    body = re.search(r"ENTRY [^\n]*\{\n(.*?)\n\}", text, re.S).group(1)
    table = {}
    for line in body.split("\n"):
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = .*? ([\w\-]+)\((.*)$", line)
        if m:
            table[m.group(1)] = (m.group(2), re.findall(
                r"%[\w.\-]+", m.group(3).split("),")[0]), line)
    return table


@pytest.mark.parametrize("heads,window,rule,flash", [
    # laguna_s_2_1's sliding layers and its full layers
    (72, 512, {"dim": 128, "rope_theta": 10000.0}, "ff_flash_win_fwd"),
    (48, None, _YARN_64, "ff_flash_fwd"),
])
def test_rotary_positions_reach_the_flash_kernels_without_a_relayout(
        one_chip, monkeypatch, heads, window, rule, flash):
    """``GroupedQueryAttention``'s forward and gradient at the cell's two
    shapes: the q product writes bfloat16 in the layout ``ff_rope`` reads
    and ``ff_rope`` the one the flash forward reads, with nothing between
    (the parent had six 302-604 MB relayouts there, in float32); the same
    on the way back, from the flash backward's dq through ``ff_rope_t``
    into the products that make dW and dx; and no ``T(2,128)``-tiled activation
    (what the lane de-interleave of ``apply_rope`` forced, PERF.md section
    6, PR 37).  The gate is left out: its float32 4-D view is another
    family, as is the float32 ``delta`` of the flash backward."""
    import importlib

    from flexflow_tpu.ops import pallas
    from flexflow_tpu.ops.attention import GroupedQueryAttention
    from flexflow_tpu.ops.base import Tensor
    from flexflow_tpu.ops.pallas import rope
    from flexflow_tpu.strategy import ParallelConfig

    fa = importlib.import_module("flexflow_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(pallas, "flash_enabled", lambda: True)
    monkeypatch.setattr(fa, "_should_interpret", lambda: False)
    monkeypatch.setattr(rope, "_should_interpret", lambda: False)
    b, s, d, hd = 2, 8192, 3072, 128
    op = GroupedQueryAttention(
        "attn", ParallelConfig((1, 1, 1), (0,)), Tensor((b, s, d),
                                                        "bfloat16"),
        heads, 8, hd, hd ** -0.5, rope=rule, window=window)
    params = {k: jax.ShapeDtypeStruct(v, jnp.float32, sharding=one_chip)
              for k, v in op._shapes().items()}
    x = jax.ShapeDtypeStruct((b, s, d), jnp.bfloat16, sharding=one_chip)

    def step(params, x):
        return jax.value_and_grad(
            lambda p, x: op.forward(p, {}, [x], True)[0].astype(
                jnp.float32).sum(), (0, 1))(params, x)

    text = jax.jit(step).lower(params, x).compile().as_text()
    entry = _entry(text)
    def calls(kernel):
        return [k for k, (opc, _, line) in entry.items()
                if opc == "custom-call" and re.search(rf"\b{kernel}\b", line)]

    def users(name):
        return [k for k, (_, ops, _) in entry.items() if name in ops]

    ropes, back_ropes = calls("ff_rope"), calls("ff_rope_t")
    assert len(ropes) == len(back_ropes) == 2, "q and k, each way"
    q = f"bf16[{b},{s},{heads * hd}]"
    assert not [line for line in text.split("\n")
                if "T(2,128)" in line and f"[{b},{s}," in line]
    # forward: product -> ff_rope -> flash
    (fwd,) = calls(flash)
    turned = entry[fwd][1][0]
    assert turned in ropes, entry[turned][2][:200]
    opcode, _, line = entry[entry[turned][1][0]]
    assert opcode == "fusion" and "dot_general" in line \
        and line.split(" = ")[1].startswith(q), line[:300]
    # backward: flash's dq (the first result of the fused backward) ->
    # ff_rope_t -> the products that make dW and dx
    (bwd,) = calls(flash.replace("fwd", "bwd"))
    (dq,) = [k for k in users(bwd) if entry[k][0] == "get-tuple-element"
             and "index=0" in entry[k][2]]
    (back,) = [r for r in users(dq) if r in back_ropes]
    assert entry[back][1][0] == dq
    for user in users(back):
        opcode, _, line = entry[user]
        assert opcode == "fusion" and "dot_general" in line, line[:300]


@pytest.mark.parametrize("heads,head_dim,rotated,dtype", [
    (72, 128, 128, "bfloat16"),     # laguna_s_2_1's sliding layers' q
    (48, 128, 64, "bfloat16"),      # and its full layers'
    # the corners of ``rope.fits`` (compiled, not timed)
    (8, 256, 256, "float32"),       # the widest head, the most VMEM
    (6, 256, 64, "bfloat16"),       # blocks of three heads
    (7, 128, 2, "float32"),         # the narrowest turn, a roll by one
])
def test_rope_compiles_at_the_cells_shapes_and_the_rules_corners(
        one_chip, monkeypatch, heads, head_dim, rotated, dtype):
    from flexflow_tpu.ops import pallas
    from flexflow_tpu.ops.pallas import rope
    from flexflow_tpu.ops.seq_gated import rope_angles

    monkeypatch.setattr(pallas, "flash_enabled", lambda: True)
    assert rope.fits(head_dim, rotated, dtype)
    b, s = 2, 2048

    def step(x):
        cos, sin = rope_angles(s, rotated, 10000.0)
        return jax.value_and_grad(
            lambda x: rope.rope_packed(x, cos, sin, heads, interpret=False
                                       ).astype(jnp.float32).sum())(x)

    text = jax.jit(step).lower(jax.ShapeDtypeStruct(
        (b, s, heads * head_dim), dtype, sharding=one_chip)
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for name in ("ff_rope", "ff_rope_t"):
        assert re.search(rf"\b{name}\b", text), name


def test_grouped_products_compile_at_the_lfm2_cells_shapes(one_chip):
    """``ff_gmm`` at the held experts' shapes of the LFM2 cell (a buffer
    of 32 768 rows, 8 experts of 2048 x 1792, bfloat16): the nine products
    of a layer, at the tiles ``_pick_tiles`` gives them."""
    from flexflow_tpu.ops.pallas import grouped_mm as gm

    m, d, f, g = 32768, 2048, 1792, 8
    assert gm._pick_tiles("gmm", m, d, f, 2, 2) == (512, 2048, 1792)
    assert gm._pick_tiles("gmm_t", m, f, d, 2, 2) == (512, 1792, 2048)
    assert gm._pick_tiles("dw", m, d, f, 2, 2) == (128, 2048, 1792)

    def shape(s, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    def step(rows, sizes, w_gate, w_up, w_down):
        def loss(rows, *w):
            y, tiles = gm.gated_ffn(rows, sizes, *w, interpret=False)
            assert tiles == (512, 2048, 1792)
            return y.astype(jnp.float32).sum()
        return jax.value_and_grad(loss, (0, 1, 2, 3))(rows, w_gate, w_up,
                                                      w_down)

    text = jax.jit(step).lower(
        shape((m, d)), shape((g,), jnp.int32), shape((g, d, f)),
        shape((g, d, f)), shape((g, f, d))).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 9
    for name in ("ff_gmm", "ff_gmm_t", "ff_gmm_dw"):
        assert re.search(rf"\b{name}\b", text), name


def test_the_lfm2_cells_step_compiles_and_fits(one_chip, monkeypatch):
    """The whole recomputed train step of ``lfm2_8b_a1b`` at published
    widths, 2 x 8192 tokens, for the described chip: the kernels the
    shapes pick are in it, the counters read what the shapes say, and
    arguments plus temporaries leave room beside the comparison's trees
    (PERF.md section 6, PR 38 quotes the bytes)."""
    import importlib
    import json
    import os

    from flexflow_tpu import obs
    from flexflow_tpu.machine import MachineModel
    from flexflow_tpu.models.lfm2 import Lfm2Config, Lfm2LM
    from flexflow_tpu.ops import pallas

    monkeypatch.setattr(pallas, "flash_enabled", lambda: True)
    for name in ("flash_attention", "grouped_mm", "fused_ce"):
        mod = importlib.import_module(f"flexflow_tpu.ops.pallas.{name}")
        monkeypatch.setattr(mod, "_should_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "lfm2_8b_a1b.json")) as f:
        config = json.load(f)
    ff = Lfm2LM(Lfm2Config.from_config(config, batch_size=2,
                                       seq_length=8192),
                MachineModel(list(one_chip.device_set)))
    before = dict(obs.snapshot()["counters"])
    params, state, opt = ff.abstract_train_state()
    toks = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)
    compiled = ff.make_train_step().lower(params, state, opt, toks,
                                          toks).compile()
    counters = obs.snapshot()["counters"]

    def counted(name):
        return counters.get(name, 0) - before.get(name, 0)

    for name, traced in (("kernels.short_conv.xla.2048x3", 6),
                         ("attn.qk_norm", 2), ("attn.kv_groups.4", 2),
                         ("kernels.rope.xla.32x64r64", 2),
                         ("kernels.rope.xla.8x64r64", 2),
                         ("kernels.flash.pack2.fused", 2),
                         ("kernels.gmm.ff_gmm.512x2048x1792", 6),
                         ("moe.route.blocked.512x256", 6),
                         ("moe.route.slots.4of8", 6),
                         ("runtime.recomputed_blocks", 8)):
        assert counted(name) == traced, name
    assert not [k for k in counters if k.startswith("kernels.rope.pallas.")
                and counted(k) and "x64r" in k]
    assert counters["moe.rows_capacity"] == 32768
    assert counters["conv.taps"] == 3
    text = compiled.as_text()
    for name in ("ff_flash_fwd", "ff_flash_bwd", "ff_gmm", "ff_gmm_t",
                 "ff_gmm_dw", "ff_ce_fwd", "ff_ce_bwd"):
        assert re.search(rf"\b{name}\b", text), name
    assert not re.search(r"\bff_rope\b", text)
    # route_rows' search of the rows' pairs is two counts, not a loop
    assert " while(" not in text
    memory = compiled.memory_analysis()
    # 772.2 M float32 parameters in and out, donated
    assert memory.argument_size_in_bytes == pytest.approx(3.089e9, rel=1e-3)
    assert memory.temp_size_in_bytes < 4.0e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 7.0e9


@pytest.mark.parametrize("window,names", [
    # smallthinker_21b_a3b's windowed layers: blocks of 1024, a band of
    # five a block over 16 384 positions
    (4096, ("ff_flash_win_fwd", "ff_flash_win_bwd")),
    # and its NoPE full layer, causal over the whole 16 384
    (None, ("ff_flash_fwd", "ff_flash_bwd")),
])
def test_flash_compiles_at_the_smallthinker_cells_shapes(one_chip, window,
                                                         names):
    import importlib

    fa = importlib.import_module("flexflow_tpu.ops.pallas.flash_attention")
    x = jax.ShapeDtypeStruct((1, 16384, 28 * 128), jnp.bfloat16,
                             sharding=one_chip)

    def step(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: fa.flash_attention_packed(
                q, k, v, 28, True, interpret=False, window=window
            ).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)

    text = jax.jit(step).lower(x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for name in names:
        assert re.search(rf"\b{name}\b", text), name


@pytest.mark.parametrize("b,s,heads,d,dv", [
    # moonlight_16b_a3b: pad256v128, the widest dq^T of the cells (8 MB)
    (2, 8192, 16, 192, 128),
    # smallthinker_21b_a3b's full layer: the longest (16 384 x 128)
    (1, 16384, 28, 128, 128),
])
def test_fused_backward_compiles_within_the_vmem_it_counts(
        one_chip, monkeypatch, b, s, heads, d, dv):
    """The flash kernels at a cell's shape compile for the described chip
    with the VMEM limit cut to what ``_fused_bwd_bytes`` counts for the
    fused backward: the count is at least Mosaic's own figure, and it is
    within the budget, so the shapes pick the fused form."""
    import importlib

    fa = importlib.import_module("flexflow_tpu.ops.pallas.flash_attention")
    g, hd, hdv, _ = fa._layout(heads, d, dv)
    block = fa._pick_block(s, False)
    count = fa._fused_bwd_bytes(block, block, s // block, g, hd, hdv, 2)
    assert count <= fa._FUSED_BWD_BYTES
    monkeypatch.setattr(fa, "_VMEM_LIMIT_BYTES", count)
    fa._make_flash.cache_clear()
    try:
        qk = jax.ShapeDtypeStruct((b, s, heads * d), jnp.bfloat16,
                                  sharding=one_chip)
        v = jax.ShapeDtypeStruct((b, s, heads * dv), jnp.bfloat16,
                                 sharding=one_chip)

        def step(q, k, v):
            return jax.value_and_grad(
                lambda q, k, v: fa.flash_attention_packed(
                    q, k, v, heads, True, interpret=False
                ).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)

        text = jax.jit(step).lower(qk, qk, v).compile().as_text()
    finally:
        fa._make_flash.cache_clear()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for name in ("ff_flash_fwd", "ff_flash_bwd"):
        assert re.search(rf"\b{name}\b", text), name


def test_reglu_grouped_products_compile_at_the_smallthinker_cells_shapes(
        one_chip):
    """``ff_gmm`` under a ReLU gate at the held experts' shapes of the
    SmallThinker cell (a buffer of 49 152 rows, 16 experts of 2560 x 768,
    bfloat16): the nine products of a layer at the tiles ``_pick_tiles``
    gives them, the ReLU and its mask in XLA between them."""
    from flexflow_tpu.ops.pallas import grouped_mm as gm

    m, d, f, g = 49152, 2560, 768, 16
    assert gm._pick_tiles("gmm", m, d, f, 2, 4) == (512, 2560, 768)

    def shape(s, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    def step(rows, sizes, w_gate, w_up, w_down):
        def loss(rows, *w):
            y, tiles = gm.gated_ffn(rows, sizes, *w, interpret=False,
                                    act="relu")
            assert tiles == (512, 2560, 768)
            return y.astype(jnp.float32).sum()
        return jax.value_and_grad(loss, (0, 1, 2, 3))(rows, w_gate, w_up,
                                                      w_down)

    text = jax.jit(step).lower(
        shape((m, d)), shape((g,), jnp.int32), shape((g, d, f)),
        shape((g, d, f)), shape((g, f, d))).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 9
    for name in ("ff_gmm", "ff_gmm_t", "ff_gmm_dw"):
        assert re.search(rf"\b{name}\b", text), name


def test_the_smallthinker_cells_step_compiles_and_fits(one_chip,
                                                       monkeypatch):
    """The whole recomputed train step of ``smallthinker_21b_a3b`` at
    published widths, one sequence of 16 384 tokens, for the described
    chip: the kernels the shapes pick are in it, the counters read what
    the shapes say, and arguments plus temporaries leave room beside the
    comparison's trees (PERF.md section 6, PR 40 quotes the bytes)."""
    import importlib
    import json
    import os

    from flexflow_tpu import obs
    from flexflow_tpu.machine import MachineModel
    from flexflow_tpu.models.smallthinker import (SmallThinkerConfig,
                                                  SmallThinkerLM)
    from flexflow_tpu.ops import pallas

    monkeypatch.setattr(pallas, "flash_enabled", lambda: True)
    for name in ("flash_attention", "grouped_mm", "fused_ce", "rope"):
        mod = importlib.import_module(f"flexflow_tpu.ops.pallas.{name}")
        monkeypatch.setattr(mod, "_should_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "smallthinker_21b_a3b.json")) as f:
        config = json.load(f)
    ff = SmallThinkerLM(SmallThinkerConfig.from_config(
        config, batch_size=1, seq_length=16384),
        MachineModel(list(one_chip.device_set)))
    before = dict(obs.snapshot()["counters"])
    params, state, opt = ff.abstract_train_state()
    toks = jax.ShapeDtypeStruct((1, 16384), jnp.int32, sharding=one_chip)
    compiled = ff.make_train_step().lower(params, state, opt, toks,
                                          toks).compile()
    counters = obs.snapshot()["counters"]

    def counted(name):
        return counters.get(name, 0) - before.get(name, 0)

    for name, traced in (("attn.kv_groups.7", 4), ("attn.rope.none", 1),
                         ("kernels.flash.pack1.fused", 1),
                         ("kernels.flash.pack1.fused.w4096", 3),
                         ("kernels.rope.pallas.28x128r128", 3),
                         ("kernels.rope.pallas.4x128r128", 3),
                         ("moe.router_input.block", 4),
                         ("moe.expert_act.relu", 4),
                         ("kernels.gmm.ff_gmm.512x2560x768", 4),
                         ("moe.route.slots.6of16", 4),
                         ("kernels.ce.fwd.512x2048", 1),
                         ("kernels.ce.fused_bwd.1024x256", 1),
                         ("runtime.recomputed_blocks", 4)):
        assert counted(name) == traced, name
    assert counters["moe.rows_capacity"] == 49152
    assert counters["attn.window"] == 4096
    text = compiled.as_text()
    for name in ("ff_flash_fwd", "ff_flash_bwd", "ff_flash_win_fwd",
                 "ff_flash_win_bwd", "ff_rope", "ff_rope_t", "ff_gmm",
                 "ff_gmm_t", "ff_gmm_dw", "ff_ce_fwd", "ff_ce_bwd"):
        assert re.search(rf"\b{name}\b", text), name
    assert " while(" not in text
    memory = compiled.memory_analysis()
    # 656.5 M float32 parameters in and out, donated
    assert memory.argument_size_in_bytes == pytest.approx(2.626e9, rel=1e-3)
    assert memory.temp_size_in_bytes < 4.5e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 7.0e9
