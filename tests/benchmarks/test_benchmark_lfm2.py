"""The ``lfm2_8b_a1b`` configuration and the cell of PR 38: the cell's CPU
rehearsal as the driver runs the benchmark (the model's loss and every
operator's applied gradient against the plain reference through
``compare.train_step``, both kinds of line), the configuration file
against the catalog row's every key, the FLOPs and kernel work from
shapes against numbers worked out by hand, and the two new readers."""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "lfm2_8b_a1b.json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "train_1chip_b2_s8192_ref2.json")) as _f:
    MIX = json.load(_f)

CELL = "lfm2_8b_a1b.train_1chip_b2_s8192_ref2"
CASES = [(CELL, 0), (CELL, 1)]
NEW = ("ops.short_conv_ms_per_step", "kernels.short_conv_roofline")
_DONE = {}

# the catalog row's ``config`` (huggingface.co/LiquidAI/LFM2-8B-A1B,
# config.json), the layer list by its periods
PUBLISHED = dict(
    model_type="lfm2_moe", hidden_size=2048, intermediate_size=7168,
    moe_intermediate_size=1792, num_hidden_layers=24, num_dense_layers=2,
    num_attention_heads=32, num_key_value_heads=8, conv_L_cache=3,
    conv_bias=False, max_position_embeddings=128000, norm_eps=1e-05,
    norm_topk_prob=True, num_experts_per_tok=4, rope_theta=1000000,
    routed_scaling_factor=1, use_expert_bias=True,
    layer_types=["conv", "conv", "full_attention"]
    + ["conv", "conv", "conv", "full_attention"] * 4
    + ["conv", "conv", "full_attention", "conv", "conv"])


def _rehearse(case):
    if case not in _DONE:
        cmd = [sys.executable] + BENCH["command"][1:] + [
            "--workload", case[0], "--seed", str(2**31 + 38), "--seconds",
            "1", "--trace", str(case[1]), "--cpu-rehearsal"]
        env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="whatever")
        env.pop("XLA_FLAGS", None)
        _DONE[case] = subprocess.run(cmd, cwd=ROOT, env=env, text=True,
                                     capture_output=True, timeout=600)
    return _DONE[case]


def _said(proc, what):
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith(f"benchmark: {what} "))
    return json.loads(line.split(" ", 2)[2])


@pytest.mark.parametrize("case", CASES, ids=lambda c: f"{c[0]}-trace{c[1]}")
def test_rehearsal_reaches_the_last_line_and_agrees_with_the_reference(case):
    proc = _rehearse(case)
    assert proc.returncode == 3, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    # the comparison found nothing: it is the rehearsal that cannot pass
    assert "benchmark: problem" not in proc.stdout
    kind = "per_layer" if case[1] else "end_to_end"
    declared = {m["name"] for m in BENCH[kind]
                if case[0] in m.get("workloads", [case[0]])}
    assert line["metrics"] and set(line["metrics"]) <= declared
    if not case[1]:
        assert set(line["metrics"]) == {"train_items_per_s_per_chip",
                                        "setup_s"}
    else:       # device metrics are the chip's: none of the new two here
        assert not set(line["metrics"]) & set(NEW)
    assert _said(proc, "compile")["in_window"]["compiles"] == 0


def test_every_operator_of_the_model_is_held_to_the_reference():
    notes = _said(_rehearse((CELL, 0)), "notes")
    c = notes["correctness"]
    tol = CONFIG["rehearsal"]["tolerance"]
    # the tied embedding, 3 x (2 norms, conv or attention), the dense
    # ffn, 2 x (router, experts) and the final norm; the head is the
    # embedding's matrix
    assert c["ops"] == 1 + 3 * 3 + 1 + 2 * 2 + 1
    assert c["ops_under_rounding_floor"] == []
    assert c["loss_rel_err"] <= tol["loss_rel"]
    assert c["grad_rel_l2"] <= tol["grad_rel_l2"]
    assert c["worst_op_grad_rel_l2"] <= tol["op_grad_rel_l2"]
    assert all(raw <= 1e-3 for _, _, raw in c["worst_ops"])
    assert notes["last_loss"] == notes["last_loss"]      # not NaN


def test_traced_rehearsal_names_the_operators_and_counts_the_mechanisms():
    proc = _rehearse((CELL, 1))
    ops = _said(proc, "operators")
    assert not ops.get("refused") and ops["attributed_share"] > 0.8
    named = {k.split("|")[0] for k, _ in ops["top"]}
    assert named & {"blk0_conv", "blk2_conv"}
    assert "blk1_attn_full" in named
    assert named & {"blk1_moe_experts", "blk2_moe_experts"}
    counters = _said(proc, "program_spans")["counters"]
    assert counters["runtime.recomputed_blocks"] % 3 == 0
    # two conv layers of 3 taps to one attention layer of 4 heads on 2
    assert counters["kernels.short_conv.xla.64x3"] \
        == 2 * counters["attn.qk_norm"] >= 2
    assert counters["conv.taps"] == 3
    assert counters["attn.kv_groups.2"] == counters["attn.qk_norm"]
    assert counters["kernels.rope.xla.4x16r16"] \
        == counters["kernels.rope.xla.2x16r16"] == counters["attn.qk_norm"]
    assert counters["kernels.gmm.ragged_dot"] >= 2
    assert counters["moe.experts_held"] == 4
    assert counters["moe.rows_capacity"] == 96   # 2 x 64 x 3 x 4/16
    # the flash, rotary and head kernels are the chip's: none here
    assert not any(k.startswith(("kernels.flash", "kernels.ce",
                                 "kernels.rope.pallas")) for k in counters)
    # PR 36's host spans split the first call by class: the new operator
    # is one of its own
    classes = dict(_said(proc, "host_timeline")["trace"]["classes"])
    assert classes["GatedShortConv"] > 0
    assert "entry.trace_op_s.GatedShortConv" in counters


def test_configuration_file_keeps_every_key_of_the_catalog_row():
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key
    rows = os.path.join(os.sep, "opt", "skills", "guides", "model-configs",
                        "architectures.jsonl")
    if os.path.isfile(rows):        # the row itself, where the guide is
        with open(rows) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-8B-A1B")
        assert CONFIG["source"] == row["source_url"]
        assert set(PUBLISHED) | {"num_experts", "vocab_size"} \
            == set(row["config"])
        differ = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
        assert differ == {"num_experts", "vocab_size"}
    assert CONFIG["published"] == {"num_hidden_layers": 24,
                                   "num_experts": 32, "vocab_size": 65536}
    assert CONFIG["reduced"] == ["num_layers", "num_experts", "vocab_size"]
    assert (CONFIG["num_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (8, 8, 16384)
    assert CONFIG["router_outputs"] == 32
    assert CONFIG["experts_held"] == [0, 8]
    assert CONFIG["rows_capacity_factor"] == 2.0
    assert CONFIG["tie_word_embeddings"] is True
    entry = next(c for c in BENCH["configs"] if c["name"] == "lfm2_8b_a1b")
    assert entry == BENCH["configs"][-1]
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmarks/configs/lfm2_8b_a1b.json"
    assert len(entry["why"]) <= 200
    assert "four chips share each layer" in CONFIG["deployment"]
    assert "layers 0-7 of 24" in CONFIG["deployment"]
    assert (CONFIG["compute_dtype"], CONFIG["param_dtype"]) \
        == ("bfloat16", "float32")
    assert CONFIG["optimizer"] == {"kind": "sgd", "learning_rate": 0.1,
                                   "weight_decay": 0.0}
    # the floors of a cut: both dense layers, a whole period and six
    # layers after the dense ones at the model's 3:1, eight experts, an
    # eighth of the rows and more
    layers = CONFIG["num_layers"]
    kinds = CONFIG["layer_types"][:layers]
    assert kinds[2:6] == ["full_attention", "conv", "conv", "conv"]
    assert (kinds.count("conv"), kinds.count("full_attention")) == (6, 2)
    assert layers - CONFIG["num_dense_layers"] >= 4
    assert CONFIG["num_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= CONFIG["published"]["vocab_size"]
    # the six things config.json does not state, and what every token
    # configuration assumes
    for key in ("qk_norm", "short_conv", "feed_forward",
                "tie_word_embeddings", "router", "bias_update",
                "rope_pairing", "optimizer", "weights", "embedding_std",
                "rows_capacity_factor", "dropout", "loss", "recomputation"):
        assert CONFIG["assumed"][key] and "TO BE" not in CONFIG["assumed"][key]
    tol = CONFIG["tolerance"]
    assert tol["why"] and "TO BE" not in tol["why"]
    assert 0 < tol["grad_rel_l2"] < tol["op_grad_rel_l2"] < 1
    assert (MIX["batch"], MIX["seq_length"], MIX["reference_chunk"]) \
        == (2, 8192, 2)
    # the rehearsal keeps both layer types, a dense and a sparse block,
    # 4 held of 16 routed
    small = CONFIG["rehearsal"]
    assert set(small["layer_types"]) == {"conv", "full_attention"}
    assert 0 < small["num_dense_layers"] < small["num_layers"]
    assert (small["num_experts"], small["router_outputs"]) == (4, 16)


def test_the_cell_and_its_entries_in_the_benchmark():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == BENCH["workloads"][-1]
    assert cell["config"] == "lfm2_8b_a1b"
    assert cell["traffic"] == "train_1chip_b2_s8192_ref2"
    assert cell["chips"] == MIX["chips"] == 1 and len(cell["why"]) <= 200
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(NEW) | {"kernels.flash_attn_roofline", "ops.moe_ms_per_step",
                       "kernels.grouped_mm_roofline", "ops.mfu",
                       "ops.attributed_share", "runtime.step_ms_p50",
                       "device.peak_hbm_gb.train"} <= listed
    assert not listed & {"ops.step_roofline", "ops.mla_ms_per_step",
                         "ops.ssm_ms_per_step", "kernels.ssd_scan_roofline",
                         "kernels.window_attn_roofline", "plan.sim_drift",
                         "executor.collective_ms_per_step",
                         "executor.regrid_ms_per_step"}
    (rate,) = [m for m in BENCH["end_to_end"]
               if m["name"] == "train_items_per_s_per_chip"]
    assert rate["workloads"][-1] == CELL
    # the cell is appended wherever it is listed
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"].index(CELL) == len(m["workloads"]) - 1
    # the two entries are somewhere after every entry the benchmark
    # had, in this order (the driver takes an entry put first or in the
    # middle as a change to the one that follows it)
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(NEW[1]) == names.index(NEW[0]) + 1
    assert names.index(NEW[0]) > names.index("runtime.gc_pause_share")
    layers = {"ops.short_conv_ms_per_step": "ops",
              "kernels.short_conv_roofline": "kernels"}
    for name in NEW:
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry == dict(_reader(name).METRIC, workloads=[CELL])
        assert entry["layer"] == layers[name]
        assert entry["source"] == "device_trace"
        assert entry["moves"] == "train_items_per_s_per_chip"
        assert _reader(name).__doc__


def test_flops_and_kernel_work_from_shapes():
    from benchmarks.flops import lfm2_8b_a1b as flops

    per_token = flops.train_flops_per_item(CONFIG, MIX)
    # M FLOP a token forward, by hand: six conv operators, two dense
    # feed-forwards, six expert layers (router and one held expert a
    # token), two attention operators (projections, and scores and
    # values over 4096.5 keys a query), the head over 16384 rows
    conv = 6 * 2 * 2048 * (3 * 2048 + 2048) / 1e6
    dense = 2 * 6 * 2048 * 7168 / 1e6
    moe = 6 * (2 * 2048 * 32 + 1.0 * 6 * 2048 * 1792) / 1e6
    attn = 2 * (2 * 2048 * (2 * 2048 + 2 * 512)
                + 4 * 32 * 64 * 4096.5) / 1e6
    head = 2 * 2048 * 16384 / 1e6
    assert [round(v) for v in (conv, dense, moe, attn, head)] \
        == [201, 176, 133, 109, 67]
    assert per_token / 3e6 == pytest.approx(conv + dense + moe + attn
                                            + head, rel=1e-9)
    assert per_token / 3e6 == pytest.approx(686.6, abs=0.05)
    assert per_token * 16384 == pytest.approx(33.7e12, rel=2e-3)
    assert flops.held_experts_per_token(CONFIG) == 1.0
    assert flops.keys_met(8192) == 8192 * 8193 // 2
    work = flops.kernel_work(CONFIG, MIX)
    assert set(work) == {"ff_flash_", "grouped_mm", "short_conv"}
    assert work["ff_flash_"]["flops"] \
        == 2 * 2 * 32 * 12.0 * 64 * flops.keys_met(8192)
    # q, o, do, dq at 32 heads, k, v, dk, dv at the true 8, bfloat16
    assert work["ff_flash_"]["bytes"] \
        == 2 * 2 * 8192 * 64 * 2 * (6 * 32 + 6 * 8)
    # 16384 pairs a layer at the balanced load: 2048 rows a held expert
    assert work["grouped_mm"]["flops"] == 6 * 9 * 2.0 * 16384 * 2048 * 1792
    assert work["grouped_mm"]["flops"] == pytest.approx(6.49e12, rel=1e-3)
    assert work["grouped_mm"]["bytes"] == 6 * 9 * 2 * (
        16384 * 2048 + 8 * 2048 * 1792 + 16384 * 1792)
    # two products forward and four backward a conv operator: 9.9 TFLOP,
    # 50 ms at 197 TFLOP/s; x, [B | C | X], C * v and the result once a
    # pass, 4.8 GB and 5.9 ms at 819 GB/s
    assert work["short_conv"]["flops"] \
        == 6 * 3 * 2.0 * 16384 * 2048 * (3 * 2048 + 2048)
    assert work["short_conv"]["flops"] / 197e12 == pytest.approx(0.0502,
                                                                 abs=2e-4)
    assert work["short_conv"]["bytes"] \
        == 6 * 2 * 2 * 16384 * (2048 + 3 * 2048 + 2048 + 2048)
    assert work["short_conv"]["bytes"] / 819e9 == pytest.approx(0.0059,
                                                                abs=1e-4)
    # a conv block costs a token 33.6 M forward beside its feed-forward,
    # an attention block 54.5 M
    def without(layer):
        cut = dict(CONFIG, num_layers=7, layer_types=[
            k for l, k in enumerate(CONFIG["layer_types"]) if l != layer])
        return flops.train_flops_per_item(cut, MIX) / 3e6

    whole = per_token / 3e6
    assert whole - without(7) == pytest.approx(33.55 + 0.13 + 22.02,
                                               abs=0.05)
    assert (whole - without(6)) - (whole - without(7)) \
        == pytest.approx(54.53 - 33.55, abs=0.05)


def test_the_reference_shares_no_code_with_the_program():
    path = os.path.join(ROOT, "benchmarks", "reference", "lfm2_8b_a1b.py")
    with open(path) as f:
        text = f.read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", text, re.M)
    assert imports and all(m.split(".")[0] == "jax" for m in imports)
    # the score matrix explicit with its mask, the experts a loop, the
    # convolution as shifted copies, the head the embedding's own matrix
    assert "jnp.where(seen, scores, -jnp.inf)" in text
    assert "lax.scan(add_one" in text and "HIGHEST" in text
    assert "u[:s - back]" in text and "table.T" in text
    assert "DENOMINATOR_EPS = 1e-6" in text


def _reader(name):
    from benchmarks import harness

    return harness.load_by_name(os.path.join(ROOT, "benchmarks",
                                             "layer_metrics"), name)


def test_the_new_readers_on_a_hand_made_trace():
    from benchmarks.flops import lfm2_8b_a1b as flops

    by_op = {("blk0_conv", "forward"): 0.08,
             ("blk0_conv", "backward"): 0.24,
             ("blk7_conv", "backward"): 0.08,
             ("blk2_attn_full", "backward"): 9.0,
             ("blk2_moe_router", "forward"): 0.1,
             ("blk3_moe_experts", "backward"): 0.5,
             ("blk0_ffn", "forward"): 9.0,
             ("blk12_conv_extra", "forward"): 9.0,
             ("lm_head", "forward"): 9.0}
    op_s = {"ff_flash_fwd.1|custom-call": 0.3,
            "ff_flash_bwd_dkv.1|custom-call": 0.5,
            "ff_gmm.3|custom-call": 7.0, "fusion.12|fusion": 7.0}
    facts = {"fences": [(0.0, 0), (1.0, 4)], "traced_steps": 4,
             "config": CONFIG, "mix": MIX, "flops": flops,
             "peaks": {"bf16_flops_per_s": 197e12,
                       "hbm_bytes_per_s": 819e9},
             "trace": {"op_s": op_s},
             "program_trace": {"on_chip": True, "steps": 4,
                               "trace": {"operator_s": by_op}}}
    work = flops.kernel_work(CONFIG, MIX)
    # 0.4 s of conv operators over four steps
    assert _reader(NEW[0]).read(facts) == pytest.approx(100.0)
    floor = work["short_conv"]["flops"] / 197e12
    assert floor > work["short_conv"]["bytes"] / 819e9
    assert _reader(NEW[1]).read(facts) == pytest.approx(
        100 * floor * 4 / 0.4)
    assert 0 < _reader(NEW[1]).read(facts) < 100
    # the accepted readers match this model's operators and kernels
    assert _reader("ops.moe_ms_per_step").read(facts) \
        == pytest.approx(150.0)
    assert _reader("kernels.grouped_mm_roofline").read(facts) \
        == pytest.approx(100 * work["grouped_mm"]["flops"] / 197e12 * 4
                         / 0.5)
    assert _reader("kernels.flash_attn_roofline").read(facts) \
        == pytest.approx(100 * work["ff_flash_"]["flops"] / 197e12
                         / (0.8 / 4))
    # nothing to read: an empty record, off the chip, a program without
    # such operators (the parent), no operator table
    for name in NEW:
        assert _reader(name).read({}) is None
        off_chip = dict(facts, program_trace=dict(
            facts["program_trace"], on_chip=False))
        assert _reader(name).read(off_chip) is None
        others = {k: v for k, v in by_op.items() if "_conv" not in k[0]
                  or k[0] == "blk12_conv_extra"}
        assert _reader(name).read(dict(facts, program_trace={
            "on_chip": True, "steps": 4,
            "trace": {"operator_s": others}})) is None
        assert _reader(name).read(dict(facts, program_trace={
            "on_chip": True, "steps": 4, "trace": None})) is None
    # a configuration whose kernel work names no short convolution
    from benchmarks.flops import granite_4_0_h_micro

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite_4_0_h_micro.json")) as f:
        other = json.load(f)
    assert _reader(NEW[1]).read(
        dict(facts, flops=granite_4_0_h_micro, config=other)) is None
