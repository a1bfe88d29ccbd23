"""The ``smallthinker_21b_a3b`` configuration and the cell of PR 40: the
cell's CPU rehearsal as the driver runs the benchmark (the model's loss
and every operator's applied gradient against the plain reference through
``compare.train_step``, both kinds of line), the configuration file
against the catalog row's every key, the FLOPs and kernel work from
shapes against numbers worked out by hand, and the new reader.  The cell,
the configuration and the metric are found by name, never by place."""

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
                       "smallthinker_21b_a3b.json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "train_1chip_b1_s16384.json")) as _f:
    MIX = json.load(_f)

CELL = "smallthinker_21b_a3b.train_1chip_b1_s16384"
CASES = [(CELL, 0), (CELL, 1)]
NEW = "ops.attn_ms_per_step"
_DONE = {}

# the catalog row's ``config`` (huggingface.co/PowerInfer/
# SmallThinker-21BA3B-Instruct, config.json) but the two keys cut here
_LAYOUT = [0, 1, 1, 1] * 13
PUBLISHED = dict(
    head_dim=128, hidden_size=2560, max_position_embeddings=16384,
    model_name="smallthinker_21b_instruct", moe_ffn_hidden_size=768,
    moe_num_active_primary_experts=6, moe_primary_router_apply_softmax=True,
    norm_topk_prob=True, num_attention_heads=28, num_hidden_layers=52,
    num_key_value_heads=4, rms_norm_eps=1e-06, rope_layout=_LAYOUT,
    rope_scaling=None, rope_theta=1500000, sliding_window_layout=_LAYOUT,
    sliding_window_size=4096, tie_word_embeddings=False)


def _rehearse(case):
    if case not in _DONE:
        cmd = [sys.executable] + BENCH["command"][1:] + [
            "--workload", case[0], "--seed", str(2**31 + 40), "--seconds",
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
    else:       # device metrics are the chip's: not the new one here
        assert NEW not in line["metrics"]
    assert _said(proc, "compile")["in_window"]["compiles"] == 0


def test_every_operator_of_the_model_is_held_to_the_reference():
    notes = _said(_rehearse((CELL, 0)), "notes")
    c = notes["correctness"]
    tol = CONFIG["rehearsal"]["tolerance"]
    # the embedding, 3 x (2 norms, router, attention, experts), the final
    # norm and the untied head
    assert c["ops"] == 1 + 3 * 5 + 1 + 1
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
    assert "blk0_attn_full" in named
    assert named & {"blk1_attn_window", "blk2_attn_window"}
    assert named & {"blk0_moe_experts", "blk1_moe_experts"}
    counters = _said(proc, "program_spans")["counters"]
    assert counters["runtime.recomputed_blocks"] % 3 == 0
    # one NoPE full layer to two rotary windowed ones, 4 query heads on 2,
    # every router before attention, every expert layer ReGLU
    layers = counters["moe.router_input.block"]
    assert layers == counters["moe.expert_act.relu"] \
        == counters["attn.kv_groups.2"] == 3 * counters["attn.rope.none"]
    assert counters["kernels.rope.xla.4x16r16"] \
        == counters["kernels.rope.xla.2x16r16"] == 2 * layers // 3
    assert counters["attn.window"] == 8
    assert counters["kernels.gmm.ragged_dot"] == layers
    assert counters["moe.experts_held"] == 4
    assert counters["moe.rows_capacity"] == 96   # 2 x 64 x 3 x 4/16
    assert not any(k.startswith(("moe.router_input.ffn",
                                 "moe.expert_act.silu")) for k in counters)
    # the flash, rotary and head kernels are the chip's: none here
    assert not any(k.startswith(("kernels.flash", "kernels.ce",
                                 "kernels.rope.pallas")) for k in counters)


def test_configuration_file_keeps_every_key_of_the_catalog_row():
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key
    rows = os.path.join(os.sep, "opt", "skills", "guides", "model-configs",
                        "architectures.jsonl")
    if os.path.isfile(rows):        # the row itself, where the guide is
        with open(rows) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert CONFIG["source"] == row["source_url"]
        cut = {"moe_num_primary_experts", "vocab_size"}
        assert set(PUBLISHED) | cut == set(row["config"])
        differ = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
        assert differ == cut
        assert {k: row["config"][k] for k in cut} == {
            k: CONFIG["published"][k] for k in cut}
    assert "model_type" not in CONFIG
    assert CONFIG["published"] == {"num_hidden_layers": 52,
                                   "moe_num_primary_experts": 64,
                                   "vocab_size": 151936}
    assert CONFIG["reduced"] == ["num_layers", "moe_num_primary_experts",
                                 "vocab_size"]
    assert (CONFIG["num_layers"], CONFIG["moe_num_primary_experts"],
            CONFIG["vocab_size"]) == (4, 16, 37984)
    assert CONFIG["router_outputs"] == 64
    assert CONFIG["experts_held"] == [0, 16]
    assert CONFIG["rows_capacity_factor"] == 2.0
    (entry,) = [c for c in BENCH["configs"]
                if c["name"] == "smallthinker_21b_a3b"]
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmarks/configs/smallthinker_21b_a3b.json"
    assert len(entry["why"]) <= 200
    for words in ("four chips share each layer", "experts 0-15",
                  "rows 0-37983 of 151936", "layers 0-3 of 52"):
        assert words in CONFIG["deployment"], words
    assert (CONFIG["compute_dtype"], CONFIG["param_dtype"]) \
        == ("bfloat16", "float32")
    assert CONFIG["optimizer"] == {"kind": "sgd", "learning_rate": 0.1,
                                   "weight_decay": 0.0}
    # the floors of a cut: a whole period and four layers, eight experts
    # and more, an eighth of the rows and more
    layers = CONFIG["num_layers"]
    assert CONFIG["sliding_window_layout"][:layers] == [0, 1, 1, 1]
    assert layers >= 4 and CONFIG["moe_num_primary_experts"] >= 8
    assert CONFIG["vocab_size"] * 4 == CONFIG["published"]["vocab_size"]
    # what config.json does not state, and what every token
    # configuration assumes, each with its reason
    for key in ("router_input", "experts", "secondary_experts",
                "no_bias_no_qk_norm", "window", "rope", "router",
                "optimizer", "weights", "embedding_std",
                "rows_capacity_factor", "balance_loss", "dropout", "loss",
                "recomputation"):
        assert CONFIG["assumed"][key] and "TO BE" not in CONFIG["assumed"][key]
    tol = CONFIG["tolerance"]
    assert tol["why"] and "TO BE" not in tol["why"]
    assert 0 < tol["grad_rel_l2"] < tol["op_grad_rel_l2"] < 1
    assert (MIX["batch"], MIX["seq_length"], MIX["reference_chunk"]) \
        == (1, 16384, 1)
    assert MIX["seq_length"] == CONFIG["max_position_embeddings"]
    # the rehearsal keeps both layer types, a window under the sequence,
    # groups of two query heads, 4 held of 16 routed
    small = CONFIG["rehearsal"]
    assert set(small["sliding_window_layout"]) == {0, 1}
    assert small["rope_layout"] == small["sliding_window_layout"]
    assert small["sliding_window_size"] < small["max_position_embeddings"]
    assert small["num_attention_heads"] // small["num_key_value_heads"] > 1
    assert (small["moe_num_primary_experts"], small["router_outputs"]) \
        == (4, 16)


def test_the_cell_and_its_entries_in_the_benchmark():
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell["config"] == "smallthinker_21b_a3b"
    assert cell["traffic"] == "train_1chip_b1_s16384"
    assert cell["chips"] == MIX["chips"] == 1 and len(cell["why"]) <= 200
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {NEW, "kernels.flash_attn_roofline",
            "kernels.window_attn_roofline", "ops.moe_ms_per_step",
            "kernels.grouped_mm_roofline", "ops.mfu", "ops.attributed_share",
            "runtime.step_ms_p50", "device.peak_hbm_gb.train"} <= listed
    assert not listed & {"ops.step_roofline", "ops.mla_ms_per_step",
                         "ops.ssm_ms_per_step", "kernels.ssd_scan_roofline",
                         "ops.short_conv_ms_per_step",
                         "kernels.short_conv_roofline", "plan.sim_drift",
                         "executor.collective_ms_per_step",
                         "executor.regrid_ms_per_step"}
    (rate,) = [m for m in BENCH["end_to_end"]
               if m["name"] == "train_items_per_s_per_chip"]
    assert CELL in rate["workloads"]
    # the new entry is after every entry the benchmark had (the driver
    # takes an entry put first or in the middle as a change to the one
    # that follows it)
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(NEW) > names.index("kernels.short_conv_roofline")
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NEW]
    assert entry == dict(_reader(NEW).METRIC, workloads=[CELL])
    assert (entry["layer"], entry["source"], entry["moves"]) \
        == ("ops", "device_trace", "train_items_per_s_per_chip")
    assert _reader(NEW).__doc__


def test_flops_and_kernel_work_from_shapes():
    from benchmarks.flops import smallthinker_21b_a3b as flops

    per_token = flops.train_flops_per_item(CONFIG, MIX)
    # M FLOP a token forward, by hand: the four layers' projections, the
    # NoPE layer's scores and values over 8192.5 keys a query, the three
    # window layers' over 3584.1, the routers, 1.5 held experts a token a
    # layer, the head over 37 984 rows
    proj = 4 * 2 * 2560 * (2 * 3584 + 2 * 512) / 1e6
    full = 4 * 28 * 128 * 16385 / 2 / 1e6
    window = 3 * 4 * 28 * 128 * flops.keys_met(16384, 4096) / 16384 / 1e6
    router = 4 * 2 * 2560 * 64 / 1e6
    moe = 4 * 1.5 * 6 * 2560 * 768 / 1e6
    head = 2 * 2560 * 37984 / 1e6
    assert [round(v, 1) for v in (proj, full, window, router, moe, head)] \
        == [167.8, 117.4, 154.1, 1.3, 70.8, 194.5]
    assert per_token / 3e6 == pytest.approx(
        proj + full + window + router + moe + head, rel=1e-9)
    assert per_token / 3e6 == pytest.approx(705.9, abs=0.05)
    assert per_token * 16384 == pytest.approx(34.7e12, rel=2e-3)
    # attention is 38.5% of the model's FLOPs at the whole context
    assert (full + window) / (per_token / 3e6) == pytest.approx(0.385,
                                                                abs=5e-4)
    assert flops.held_experts_per_token(CONFIG) == 1.5
    assert flops.keys_met(16384, 4096) == 4096 * 4097 // 2 + 12288 * 4096
    work = flops.kernel_work(CONFIG, MIX)
    assert set(work) == {"ff_flash_", "ff_flash_win_", "grouped_mm"}
    assert work["ff_flash_"]["flops"] == 28 * 12.0 * 128 * (
        flops.keys_met(16384) + 3 * flops.keys_met(16384, 4096))
    assert work["ff_flash_win_"]["flops"] \
        == 3 * 28 * 12.0 * 128 * flops.keys_met(16384, 4096)
    # q, o, do, dq at 28 heads, k, v, dk, dv at the true 4, bfloat16
    assert work["ff_flash_"]["bytes"] \
        == 4 * 16384 * 128 * 2 * (6 * 28 + 6 * 4)
    assert work["ff_flash_win_"]["bytes"] \
        == 3 * work["ff_flash_"]["bytes"] // 4
    # 24 576 pairs a layer at the balanced load: 1536 rows a held expert
    assert work["grouped_mm"]["flops"] == 4 * 9 * 2.0 * 24576 * 2560 * 768
    assert work["grouped_mm"]["bytes"] == 4 * 9 * 2 * (
        24576 * 2560 + 16 * 2560 * 768 + 24576 * 768)


def test_the_reference_shares_no_code_with_the_program():
    path = os.path.join(ROOT, "benchmarks", "reference",
                        "smallthinker_21b_a3b.py")
    with open(path) as f:
        text = f.read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", text, re.M)
    assert imports and all(m.split(".")[0] == "jax" for m in imports)
    # the score matrix explicit with its mask, the experts a loop under
    # ReLU, the router read from the first norm
    assert "jnp.where(seen, scores, -jnp.inf)" in text
    assert "lax.scan(add_one" in text and "HIGHEST" in text
    assert "jax.nn.relu(_mm(m, w_gate))" in text
    assert '_normed(p, i, "norm1", row, c)' in text


def _reader(name):
    from benchmarks import harness

    return harness.load_by_name(os.path.join(ROOT, "benchmarks",
                                             "layer_metrics"), name)


def test_the_new_reader_on_a_hand_made_trace():
    from benchmarks.flops import smallthinker_21b_a3b as flops

    by_op = {("blk0_attn_full", "forward"): 0.1,
             ("blk0_attn_full", "backward"): 0.3,
             ("blk1_attn_window", "backward"): 0.2,
             ("blk3_attn_window", "forward"): 0.2,
             ("blk0_moe_router", "forward"): 0.1,
             ("blk2_moe_experts", "backward"): 0.5,
             ("blk12_attn_window_extra", "forward"): 9.0,
             ("lm_head", "forward"): 9.0}
    op_s = {"ff_flash_fwd.1|custom-call": 0.1,
            "ff_flash_win_bwd_dkv.1|custom-call": 0.3,
            "ff_gmm.3|custom-call": 7.0, "fusion.12|fusion": 7.0}
    facts = {"fences": [(0.0, 0), (1.0, 4)], "traced_steps": 4,
             "config": CONFIG, "mix": MIX, "flops": flops,
             "peaks": {"bf16_flops_per_s": 197e12,
                       "hbm_bytes_per_s": 819e9},
             "trace": {"op_s": op_s},
             "program_trace": {"on_chip": True, "steps": 4,
                               "trace": {"operator_s": by_op}}}
    work = flops.kernel_work(CONFIG, MIX)
    # 0.8 s of attention operators over four steps
    assert _reader(NEW).read(facts) == pytest.approx(200.0)
    # the accepted readers match this model's operators and kernels
    assert _reader("ops.moe_ms_per_step").read(facts) \
        == pytest.approx(150.0)
    assert _reader("kernels.grouped_mm_roofline").read(facts) \
        == pytest.approx(100 * work["grouped_mm"]["flops"] / 197e12 * 4
                         / 0.5)
    assert _reader("kernels.flash_attn_roofline").read(facts) \
        == pytest.approx(100 * work["ff_flash_"]["flops"] / 197e12
                         / (0.4 / 4))
    assert _reader("kernels.window_attn_roofline").read(facts) \
        == pytest.approx(100 * work["ff_flash_win_"]["flops"] / 197e12
                         / (0.3 / 4))
    # nothing to read: an empty record, off the chip, a program without
    # such operators (the parent), no operator table
    assert _reader(NEW).read({}) is None
    off_chip = dict(facts, program_trace=dict(
        facts["program_trace"], on_chip=False))
    assert _reader(NEW).read(off_chip) is None
    others = {k: v for k, v in by_op.items() if "_attn_" not in k[0]
              or k[0] == "blk12_attn_window_extra"}
    assert _reader(NEW).read(dict(facts, program_trace={
        "on_chip": True, "steps": 4,
        "trace": {"operator_s": others}})) is None
    assert _reader(NEW).read(dict(facts, program_trace={
        "on_chip": True, "steps": 4, "trace": None})) is None
