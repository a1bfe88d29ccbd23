"""The ``ling_3_0_flash`` configuration and its cell: the cell's CPU
rehearsal as the driver runs the benchmark (the model's loss and every
operator's applied gradient against the plain reference through
``compare.train_step``, both kinds of line), the configuration file
against the catalog row's every key, the FLOPs and kernel work from
shapes against numbers worked out by hand, and the two new readers.  The
cell, the configuration and the metrics are found by name, never by
place."""

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
                       "ling_3_0_flash.json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "train_1chip_b1_s16384.json")) as _f:
    MIX = json.load(_f)

CELL = "ling_3_0_flash.train_1chip_b1_s16384"
CASES = [(CELL, 0), (CELL, 1)]
NEW = ("ops.kda_ms_per_step", "kernels.kda_scan_roofline")
_DONE = {}
CUT = {"num_experts": 8, "vocab_size": 19648}


def _rehearse(case):
    if case not in _DONE:
        cmd = [sys.executable] + BENCH["command"][1:] + [
            "--workload", case[0], "--seed", str(2**31 + 44), "--seconds",
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
    else:       # device metrics are the chip's: not the new ones here
        assert not set(NEW) & set(line["metrics"])
    assert _said(proc, "compile")["in_window"]["compiles"] == 0


def test_every_operator_of_the_model_is_held_to_the_reference():
    notes = _said(_rehearse((CELL, 0)), "notes")
    c = notes["correctness"]
    tol = CONFIG["rehearsal"]["tolerance"]
    # the embedding; blk0: 2 norms, KDA in and out, the dense
    # feed-forward; blk1: 2 norms, KDA in and out, router, experts,
    # shared; blk2: 2 norms, MLA, router, experts, shared; the final norm
    # and the untied head (the scan has no parameters)
    assert c["ops"] == 1 + 5 + 7 + 6 + 1 + 1
    assert c["ops_under_rounding_floor"] == []
    assert c["loss_rel_err"] <= tol["loss_rel"]
    assert c["grad_rel_l2"] <= tol["grad_rel_l2"]
    assert c["worst_op_grad_rel_l2"] <= tol["op_grad_rel_l2"]
    assert all(raw <= 1e-3 for _, _, raw in c["worst_ops"])
    assert notes["last_loss"] == notes["last_loss"]      # not NaN


def test_traced_rehearsal_names_the_operators_and_counts_the_mechanisms():
    proc = _rehearse((CELL, 1))
    ops = _said(proc, "operators")
    # the scan's loops keep some bookkeeping outside any operator on the
    # CPU (0.77 of the busy time attributed in a run beside five other
    # test workers; 0.93 on the chip)
    assert not ops.get("refused") and ops["attributed_share"] > 0.6
    named = {k.split("|")[0] for k, _ in ops["top"]}
    assert named & {"blk0_kda_scan", "blk1_kda_scan"}
    assert "blk2_mla" in named
    counters = _said(proc, "program_spans")["counters"]
    assert counters["runtime.recomputed_blocks"] % 3 == 0
    # two KDA layers in chunks of the rehearsal's whole 32 positions, one
    # MLA layer with its head norms, gate and interleaved rotary, two
    # expert layers routed within 2 of 4 groups
    layers = counters["kernels.kda.xla.4x16c32"]
    assert layers == 2 * counters["attn.head_gate"] \
        == 2 * counters["attn.qk_norm"] \
        == 2 * counters["attn.rope.interleave"] \
        == counters["moe.router_groups.2of4"]
    assert (counters["kda.chunk"], counters["kda.chunks_per_sequence"],
            counters["kda.gate_lower_bound"]) == (32, 1, -5.0)
    assert counters["kernels.rope.xla.4x24r8"] == layers
    assert counters["moe.experts_held"] == 4
    assert counters["moe.rows_capacity"] == 128   # 2 x 64 x 4 x 4/16
    # the flash and head kernels are the chip's: none here
    assert not any(k.startswith(("kernels.flash", "kernels.ce"))
                   for k in counters)


def test_configuration_file_keeps_every_key_of_the_catalog_row():
    rows = os.path.join(os.sep, "opt", "skills", "guides", "model-configs",
                        "architectures.jsonl")
    if os.path.isfile(rows):        # the row itself, where the guide is
        with open(rows) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ling-3.0-flash")
        assert CONFIG["source"] == row["source_url"]
        differ = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
        assert differ == set(CUT)
        assert {k: row["config"][k] for k in CUT} == {
            k: CONFIG["published"][k] for k in CUT}
    assert CONFIG["model_type"] == "bailing_hybrid"
    assert CONFIG["published"] == {"num_hidden_layers": 42,
                                   "num_experts": 512, "vocab_size": 157184}
    assert CONFIG["reduced"] == ["num_layers", "num_experts", "vocab_size"]
    assert {k: CONFIG[k] for k in CUT} == CUT
    assert (CONFIG["num_layers"], CONFIG["num_hidden_layers"],
            CONFIG["router_outputs"], CONFIG["experts_held"],
            CONFIG["rows_capacity_factor"]) == (6, 42, 512, [0, 8], 2.0)
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "ling_3_0_flash"]
    assert entry == BENCH["configs"][-1]
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmarks/configs/ling_3_0_flash.json"
    assert len(entry["why"]) <= 200
    for words in ("64 chips share each layer", "experts 0-7",
                  "rows 0-19647 of 157184", "layers 0-5 of 42"):
        assert words in CONFIG["deployment"], words
    assert (CONFIG["compute_dtype"], CONFIG["param_dtype"]) \
        == ("bfloat16", "float32")
    assert CONFIG["optimizer"] == {"kind": "sgd", "learning_rate": 0.1,
                                   "weight_decay": 0.0}
    # the cut: one whole period of six layers, both dense ones, 8 experts
    # all in group 0 of the router's 8, an eighth of the rows
    layers = CONFIG["num_layers"]
    assert layers == CONFIG["layer_group_size"] \
        == CONFIG["num_hidden_layers"] // 7
    assert CONFIG["first_k_dense_replace"] == 2
    group = CONFIG["router_outputs"] // CONFIG["n_group"]
    assert CONFIG["experts_held"][1] <= group
    assert CONFIG["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]
    assert not any(CONFIG["expert_swiglu_limit_list"][:layers])
    assert not any(CONFIG["share_expert_swiglu_limit_list"][:layers])
    for key in ("kda_decay", "layer_pattern", "mla_norm_and_gate",
                "kda_output_gate", "rope", "router", "balance_loss", "mtp",
                "swiglu_limits", "optimizer", "weights", "embedding_std",
                "rows_capacity_factor", "dropout", "loss", "recomputation"):
        assert CONFIG["assumed"][key] and "TO BE" not in CONFIG["assumed"][key]
    tol = CONFIG["tolerance"]
    assert tol["why"] and "TO BE" not in tol["why"]
    assert 0 < tol["grad_rel_l2"] < tol["op_grad_rel_l2"] < 1
    assert (MIX["batch"], MIX["seq_length"], MIX["reference_chunk"]) \
        == (1, 16384, 1)
    # the rehearsal keeps every kind of block: KDA dense, KDA with
    # experts, MLA with experts, routed within groups
    small = CONFIG["rehearsal"]
    assert (small["num_layers"], small["layer_group_size"],
            small["first_k_dense_replace"]) == (3, 3, 1)
    assert (small["n_group"], small["topk_group"],
            small["router_outputs"]) == (4, 2, 16)


def test_the_cell_and_its_entries_in_the_benchmark():
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell == BENCH["workloads"][-1]
    assert cell["config"] == "ling_3_0_flash"
    assert cell["traffic"] == "train_1chip_b1_s16384"
    assert cell["chips"] == MIX["chips"] == 1 and len(cell["why"]) <= 200
    assert "64x" in cell["why"]
    cells = [w["name"] for w in BENCH["workloads"]]
    (rate,) = [m for m in BENCH["end_to_end"]
               if m["name"] == "train_items_per_s_per_chip"]
    assert rate["workloads"] == cells
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    every = {m["name"] for m in BENCH["per_layer"]
             if m.get("workloads") == cells}
    assert listed == every | {"ops.mla_ms_per_step", "ops.moe_ms_per_step",
                              "kernels.grouped_mm_roofline",
                              "kernels.flash_attn_roofline"} | set(NEW)
    # what other configurations' tests pin to their own cell stays theirs
    assert not listed & {"ops.attn_ms_per_step",
                         "kernels.window_attn_roofline",
                         "ops.short_conv_ms_per_step",
                         "kernels.short_conv_roofline",
                         "ops.ssm_ms_per_step", "kernels.ssd_scan_roofline",
                         "ops.step_roofline"}
    # appended: the cell last wherever it was added, the new entries last
    for m in BENCH["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL, m["name"]
    assert [m["name"] for m in BENCH["per_layer"]][-2:] == list(NEW)
    for name in NEW:
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry == dict(_reader(name).METRIC, workloads=[CELL])
        assert (entry["source"], entry["moves"]) \
            == ("device_trace", "train_items_per_s_per_chip")
        assert _reader(name).__doc__


def test_flops_and_kernel_work_from_shapes():
    from benchmarks.flops import ling_3_0_flash as flops

    per_token = flops.train_flops_per_item(CONFIG, MIX)
    # M FLOP a token forward, by hand: five KDA layers (the product in to
    # 5 x 4096 + 32 columns, the product out, the chunked scan at chunks
    # of 64 over 32 heads of 128), one MLA layer at 16384 positions, two
    # dense feed-forwards of 6144, four expert layers (router, shared
    # expert, 1/8 of a held expert a token), the head over 19 648 rows
    kda = 5 * (2 * 2560 * (5 * 4096 + 32) + 2 * 4096 * 2560
               + 32 * (8 * 64 * 128 + 8 * 128 ** 2 + 2 * 128 ** 3 / 64)) / 1e6
    mla = (2 * (2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 2560 * 32
                + 32 * 128 * 2560) + 2 * 32 * 320 * 16385 / 2) / 1e6
    dense = 2 * 6 * 2560 * 6144 / 1e6
    moe = 4 * (2 * 2560 * 512 + 6 * 2560 * 768 + 0.125 * 6 * 2560 * 768) \
        / 1e6
    head = 2 * 2560 * 19648 / 1e6
    assert [round(v, 1) for v in (kda, mla, dense, moe, head)] \
        == [671.9, 231.7, 188.7, 63.6, 100.6]
    assert per_token / 3e6 == pytest.approx(kda + mla + dense + moe + head,
                                            rel=1e-9)
    assert per_token * 16384 == pytest.approx(61.76e12, rel=1e-3)
    # the scan's arithmetic at the docstring's three chunks
    assert flops.kda_scan_flops_per_token(CONFIG, 16384) == 32 * 262144
    assert flops.kda_chunk(16384) == 64 and flops.kda_chunk(20) == 32
    assert flops.held_experts_per_token(CONFIG) == 0.125
    work = flops.kernel_work(CONFIG, MIX)
    assert set(work) == {"ff_flash_", "grouped_mm", "kda_scan"}
    assert work["kda_scan"]["flops"] == 5 * 3 * 16384 * 32 * 262144
    assert work["kda_scan"]["bytes"] == 5 * 16384 * (
        11 * 4096 * 2 + 3 * 4 * 4096 + 3 * 4 * 32)
    assert work["ff_flash_"]["flops"] == 32 * 2.0 * 16384 ** 2 * 960 / 2
    assert work["ff_flash_"]["bytes"] == 32 * 16384 * 2 * 6 * 320
    # 2048 pairs a layer at the balanced load: 256 rows a held expert
    assert work["grouped_mm"]["flops"] == 4 * 9 * 2.0 * 2048 * 2560 * 768
    assert work["grouped_mm"]["bytes"] == 4 * 9 * 2 * (
        2048 * 2560 + 8 * 2560 * 768 + 2048 * 768)


def test_the_reference_shares_no_code_with_the_program():
    path = os.path.join(ROOT, "benchmarks", "reference", "ling_3_0_flash.py")
    with open(path) as f:
        text = f.read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", text, re.M)
    assert imports and all(m.split(".")[0] == "jax" for m in imports)
    # the delta rule step by step, the score matrix explicit with its
    # mask, the groups written out, the experts a loop
    assert "lax.scan(step, state, xs)" in text
    assert "jnp.where(seen, scores, -jnp.inf)" in text
    assert "jnp.where(in_kept, ranked, -jnp.inf)" in text
    assert "lax.scan(add_one" in text and "HIGHEST" in text


def _reader(name):
    from benchmarks import harness

    return harness.load_by_name(os.path.join(ROOT, "benchmarks",
                                             "layer_metrics"), name)


def test_the_new_readers_on_a_hand_made_trace():
    from benchmarks.flops import ling_3_0_flash as flops

    by_op = {("blk0_kda_in", "forward"): 0.1,
             ("blk0_kda_scan", "forward"): 0.2,
             ("blk0_kda_scan", "backward"): 0.3,
             ("blk4_kda_out", "backward"): 0.2,
             ("blk5_mla", "forward"): 0.4,
             ("blk2_moe_experts", "backward"): 0.5,
             ("blk12_kda_scan_extra", "forward"): 9.0,
             ("lm_head", "forward"): 9.0}
    op_s = {"ff_flash_fwd.1|custom-call": 0.1,
            "ff_flash_bwd.1|custom-call": 0.3,
            "ff_gmm.3|custom-call": 7.0, "fusion.12|fusion": 7.0}
    facts = {"fences": [(0.0, 0), (1.0, 4)], "traced_steps": 4,
             "config": CONFIG, "mix": MIX, "flops": flops,
             "peaks": {"bf16_flops_per_s": 197e12,
                       "hbm_bytes_per_s": 819e9},
             "trace": {"op_s": op_s},
             "program_trace": {"on_chip": True, "steps": 4,
                               "trace": {"operator_s": by_op}}}
    work = flops.kernel_work(CONFIG, MIX)
    # 0.8 s of KDA operators over four steps
    assert _reader(NEW[0]).read(facts) == pytest.approx(200.0)
    # the scan's 0.5 s against the larger of its FLOPs' and bytes' floors
    floor = max(work["kda_scan"]["flops"] / 197e12,
                work["kda_scan"]["bytes"] / 819e9)
    assert floor == work["kda_scan"]["bytes"] / 819e9
    assert _reader(NEW[1]).read(facts) == pytest.approx(
        100 * floor * 4 / 0.5)
    # the accepted readers match this model's operators and kernels
    assert _reader("ops.mla_ms_per_step").read(facts) \
        == pytest.approx(100.0)
    assert _reader("ops.moe_ms_per_step").read(facts) \
        == pytest.approx(125.0)
    assert _reader("kernels.flash_attn_roofline").read(facts) \
        == pytest.approx(100 * work["ff_flash_"]["flops"] / 197e12
                         / (0.4 / 4))
    # nothing to read: an empty record, off the chip, a program without
    # such operators (the parent), no operator table
    for name in NEW:
        assert _reader(name).read({}) is None
        assert _reader(name).read(dict(facts, program_trace=dict(
            facts["program_trace"], on_chip=False))) is None
        others = {k: v for k, v in by_op.items() if "_kda_" not in k[0]
                  or k[0] == "blk12_kda_scan_extra"}
        assert _reader(name).read(dict(facts, program_trace={
            "on_chip": True, "steps": 4,
            "trace": {"operator_s": others}})) is None
        assert _reader(name).read(dict(facts, program_trace={
            "on_chip": True, "steps": 4, "trace": None})) is None
