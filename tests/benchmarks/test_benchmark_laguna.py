"""The ``laguna_s_2_1`` configuration and the cell of PR 34: the cell's
CPU rehearsal as the driver runs the benchmark (the model's loss and every
operator's applied gradient against the plain reference through
``compare.train_step``, both kinds of line), the configuration file
against the catalog row's every key, the FLOPs and kernel work from
shapes (the window's count against a brute-force one), and the new
reader (one: a second per-layer entry would push PR 26's seven out of the
place ``test_benchmark_program_trace.py`` pins them to)."""

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
                       "laguna_s_2_1.json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "train_1chip_b2_s8192_ref2.json")) as _f:
    MIX = json.load(_f)

CELL = "laguna_s_2_1.train_1chip_b2_s8192_ref2"
CASES = [(CELL, 0), (CELL, 1)]
_DONE = {}

# the catalog row's ``config`` (huggingface.co/poolside/Laguna-S-2.1,
# config.json), the per-layer lists by their period
PUBLISHED = dict(
    model_type="laguna", hidden_size=3072, intermediate_size=12288,
    num_hidden_layers=48, num_attention_heads=48, num_key_value_heads=8,
    head_dim=128, max_position_embeddings=1048576, attention_bias=False,
    rms_norm_eps=1e-06, num_experts_per_tok=10, moe_intermediate_size=1024,
    shared_expert_intermediate_size=1024, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[0], tie_word_embeddings=False,
    gating="per-head", sliding_window=512,
    moe_apply_router_weight_on_input=False, moe_routed_scaling_factor=2.5,
    moe_router_logit_softcapping=0,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    layer_types=["full_attention"] + (["sliding_attention"] * 3
                                      + ["full_attention"]) * 11
    + ["sliding_attention"] * 3,
    mlp_layer_types=["dense"] + ["sparse"] * 47,
    gating_types=["per_head"] * 48,
    num_attention_heads_per_layer=[48] + ([72] * 3 + [48]) * 11 + [72] * 3)


def _rehearse(case):
    if case not in _DONE:
        cmd = [sys.executable] + BENCH["command"][1:] + [
            "--workload", case[0], "--seed", str(2**31 + 34), "--seconds",
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
    assert _said(proc, "compile")["in_window"]["compiles"] == 0


def test_every_operator_of_the_model_is_held_to_the_reference():
    notes = _said(_rehearse((CELL, 0)), "notes")
    c = notes["correctness"]
    tol = CONFIG["rehearsal"]["tolerance"]
    # embed, 3 x (2 norms, attention), the dense ffn, 2 x (router,
    # experts, shared), the final norm and the head
    assert c["ops"] == 1 + 3 * 3 + 1 + 2 * 3 + 2
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
    assert named & {"blk1_attn_window", "blk2_attn_window"}
    assert "blk0_attn_full" in named
    assert named & {"blk1_moe_experts", "blk2_moe_experts"}
    counters = _said(proc, "program_spans")["counters"]
    assert counters["runtime.recomputed_blocks"] % 3 == 0
    # one full layer of 4 heads and two sliding ones of 6, on 2
    assert counters["attn.kv_groups.2"] >= 1
    assert counters["attn.kv_groups.3"] >= 2
    assert counters["attn.kv_groups.3"] == 2 * counters["attn.kv_groups.2"]
    assert counters["attn.window"] == 8
    assert counters["kernels.gmm.ragged_dot"] >= 2
    assert counters["moe.experts_held"] == 4
    assert counters["moe.rows_capacity"] == 96   # 2 x 64 x 3 x 4/16
    # the flash and head kernels are the chip's: none on this backend
    assert not any(k.startswith(("kernels.flash", "kernels.ce"))
                   for k in counters)


def test_configuration_file_keeps_every_key_of_the_catalog_row():
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key
    rows = os.path.join(os.sep, "opt", "skills", "guides", "model-configs",
                        "architectures.jsonl")
    if os.path.isfile(rows):        # the row itself, where the guide is
        with open(rows) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Laguna-S-2.1")
        assert CONFIG["source"] == row["source_url"]
        differ = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
        assert differ == {"num_experts", "vocab_size"}
    assert CONFIG["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 256,
                                   "vocab_size": 100352}
    assert CONFIG["reduced"] == ["num_layers", "num_experts", "vocab_size"]
    assert (CONFIG["num_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (5, 8, 12544)
    assert CONFIG["router_outputs"] == 256
    assert CONFIG["experts_held"] == [0, 8]
    entry = next(c for c in BENCH["configs"] if c["name"] == "laguna_s_2_1")
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert "over 32 chips, 8 a chip" in CONFIG["deployment"]
    assert "over 8 chips" in CONFIG["deployment"]
    assert (CONFIG["compute_dtype"], CONFIG["param_dtype"]) \
        == ("bfloat16", "float32")
    assert CONFIG["optimizer"]["kind"] == "sgd"
    # the floors of a cut: the dense layer and one whole period (three
    # sliding layers to one full), eight experts, an eighth of the rows
    layers = CONFIG["num_layers"]
    assert CONFIG["layer_types"][1:layers] \
        == ["sliding_attention"] * 3 + ["full_attention"]
    assert CONFIG["mlp_layer_types"][:layers] == ["dense"] + ["sparse"] * 4
    assert CONFIG["num_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= CONFIG["published"]["vocab_size"]
    # the six things config.json does not state, and what every token
    # configuration assumes
    for key in ("router", "shared_expert", "attention_gate", "qk_norm",
                "feed_forward", "rope_pairing", "optimizer", "weights",
                "embedding_std", "rows_capacity_factor", "dropout",
                "recomputation"):
        assert CONFIG["assumed"][key] and "TO BE" not in CONFIG["assumed"][key]
    tol = CONFIG["tolerance"]
    assert tol["why"] and "TO BE" not in tol["why"]
    assert 0 < tol["grad_rel_l2"] < tol["op_grad_rel_l2"] < 1
    assert (MIX["batch"], MIX["seq_length"], MIX["reference_chunk"]) \
        == (2, 8192, 2)
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == MIX["chips"] == 1 and len(cell["why"]) <= 200
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"kernels.window_attn_roofline",
            "kernels.flash_attn_roofline", "ops.moe_ms_per_step",
            "kernels.grouped_mm_roofline", "ops.mfu",
            "ops.attributed_share"} <= listed
    assert not listed & {"ops.step_roofline", "ops.mla_ms_per_step",
                         "ops.ssm_ms_per_step", "kernels.ssd_scan_roofline",
                         "plan.sim_drift"}
    entry = BENCH["per_layer"][-1]          # the one entry this PR adds
    assert entry["name"] == "kernels.window_attn_roofline"
    assert entry == dict(_reader(entry["name"]).METRIC, workloads=[CELL])
    # PR 26's seven stay where test_benchmark_program_trace.py pins them
    assert [m["name"] for m in BENCH["per_layer"]].index(
        "executor.regrid_ms_per_step") >= len(BENCH["per_layer"]) - 7


def test_flops_and_kernel_work_from_shapes():
    from benchmarks.flops import laguna_s_2_1 as flops

    per_token = flops.train_flops_per_item(CONFIG, MIX)
    assert per_token / 3e6 == pytest.approx(1220.72, abs=0.01)
    assert per_token * 16384 == pytest.approx(60.0e12, rel=1e-3)
    assert flops.held_experts_per_token(CONFIG) == 0.3125
    # the window's count against one pair after another
    for s, window in ((40, 7), (16, 16), (9, 30), (33, 1), (12, None)):
        brute = sum(1 for i in range(s) for t in range(s) if t <= i
                    and (window is None or i - t < window))
        assert flops.keys_met(s, window) == brute, (s, window)
    assert flops.keys_met(8192, 512) == 512 * 513 // 2 + 7680 * 512
    work = flops.kernel_work(CONFIG, MIX)
    win = 3 * 2 * 72 * 12.0 * 128 * flops.keys_met(8192, 512)
    full = 2 * 2 * 48 * 12.0 * 128 * flops.keys_met(8192)
    assert work["ff_flash_win_"]["flops"] == win
    assert work["ff_flash_"]["flops"] == win + full
    # the window needs 0.19 of a full layer's score FLOPs, at 1.5 times
    # the heads
    assert (win / 3) / (full / 2) == pytest.approx(1.5 * 0.121, rel=0.01)
    # q, o, do, dq at the layer's heads, k, v, dk, dv at the true 8
    assert work["ff_flash_win_"]["bytes"] \
        == 3 * 2 * 8192 * 128 * 2 * (6 * 72 + 6 * 8)
    assert work["ff_flash_"]["bytes"] - work["ff_flash_win_"]["bytes"] \
        == 2 * 2 * 8192 * 128 * 2 * (6 * 48 + 6 * 8)
    assert work["grouped_mm"]["flops"] == 4 * 9 * 2.0 * 5120 * 3072 * 1024
    # a sliding layer 318 M a token forward, a full expert layer 366 M
    def without(layer):
        keep = [l for l in range(5) if l != layer]
        cut = dict(CONFIG, num_layers=4)
        for key in ("layer_types", "mlp_layer_types",
                    "num_attention_heads_per_layer"):
            cut[key] = [CONFIG[key][l] for l in keep]
        return flops.train_flops_per_item(cut, MIX) / 3e6

    whole = per_token / 3e6
    assert whole - without(1) == pytest.approx(
        2 * 3072 * (2 * 9216 + 2048 + 72) / 1e6 + 18.29 + 1.57 + 18.87
        + 0.3125 * 18.87, abs=0.05)
    assert (whole - without(4)) - (whole - without(1)) == pytest.approx(
        100.68 - 18.29 - 2 * 3072 * (2 * 3072 + 24) / 1e6, abs=0.05)


def test_the_reference_shares_no_code_with_the_program():
    path = os.path.join(ROOT, "benchmarks", "reference", "laguna_s_2_1.py")
    with open(path) as f:
        text = f.read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", text, re.M)
    assert imports and all(m.split(".")[0] in ("jax", "math")
                           for m in imports)
    # the score matrix explicit with its mask, the experts a loop
    assert "jnp.where(seen, scores, -jnp.inf)" in text
    assert "lax.scan(add_one" in text and "HIGHEST" in text


def _reader(name):
    from benchmarks import harness

    return harness.load_by_name(os.path.join(ROOT, "benchmarks",
                                             "layer_metrics"), name)


def test_the_new_reader_on_a_hand_made_trace():
    from benchmarks.flops import laguna_s_2_1 as flops

    by_op = {("blk1_attn_window", "forward"): 0.2,
             ("blk1_moe_router", "forward"): 0.1,
             ("blk2_moe_experts", "backward"): 0.5,
             ("blk0_attn_full", "backward"): 9.0,
             ("lm_head", "forward"): 9.0}
    op_s = {"ff_flash_win_fwd.2|custom-call": 0.03,
            "ff_flash_win_bwd_dkv.1|custom-call": 0.05,
            "transpose_jvp_ff_flash_win_bwd_dq__|custom-call": 0.02,
            "ff_flash_fwd.1|custom-call": 7.0,
            "ff_gmm.3|custom-call": 7.0, "fusion.12|fusion": 7.0}
    facts = {"fences": [(0.0, 0), (1.0, 4)], "traced_steps": 4,
             "config": CONFIG, "mix": MIX, "flops": flops,
             "peaks": {"bf16_flops_per_s": 197e12,
                       "hbm_bytes_per_s": 819e9},
             "trace": {"op_s": op_s},
             "program_trace": {"on_chip": True, "steps": 4,
                               "trace": {"operator_s": by_op}}}
    # the accepted expert-layer readers match this model's operators
    assert _reader("ops.moe_ms_per_step").read(facts) \
        == pytest.approx(150.0)
    assert _reader("kernels.grouped_mm_roofline").read(facts) \
        == pytest.approx(100 * flops.kernel_work(CONFIG, MIX)[
            "grouped_mm"]["flops"] / 197e12 * 4 / 0.5)
    need = flops.kernel_work(CONFIG, MIX)["ff_flash_win_"]
    floor = need["flops"] / 197e12
    assert floor > need["bytes"] / 819e9
    assert _reader("kernels.window_attn_roofline").read(facts) \
        == pytest.approx(100 * floor / (0.1 / 4))
    # the accepted flash share sees the windowed kernels too
    assert _reader("kernels.flash_attn_roofline").read(facts) \
        == pytest.approx(100 * flops.kernel_work(CONFIG, MIX)["ff_flash_"][
            "flops"] / 197e12 / (7.1 / 4))
    # nothing to read: an empty record, a program without such kernels
    # (the parent), no trace
    assert _reader("kernels.window_attn_roofline").read({}) is None
    for trace in ({"op_s": {"ff_flash_fwd.1|custom-call": 1.0}}, None):
        assert _reader("kernels.window_attn_roofline").read(
            dict(facts, trace=trace)) is None
    # a configuration whose kernel work names no windowed kernels
    from benchmarks.flops import granite_4_0_h_micro

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite_4_0_h_micro.json")) as f:
        other = json.load(f)
    assert _reader("kernels.window_attn_roofline").read(
        dict(facts, flops=granite_4_0_h_micro, config=other)) is None
