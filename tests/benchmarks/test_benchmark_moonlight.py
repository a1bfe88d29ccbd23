"""The ``moonlight_16b_a3b`` configuration and the two cells of PR 28:
the cell's CPU rehearsal as the driver runs the benchmark (the model's
loss and every operator's applied gradient against the plain reference
through ``compare.train_step``, both kinds of line), the one-chip AlexNet
control, the configuration file against the published sizes, the FLOPs
and kernel work from shapes, and the three readers."""

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
                       "moonlight_16b_a3b.json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "train_1chip_b2_s8192.json")) as _f:
    MIX = json.load(_f)

CELL = "moonlight_16b_a3b.train_1chip_b2_s8192"
CONTROL = "alexnet_owt.train_1chip_b2048"
CASES = [(CELL, 0), (CELL, 1), (CONTROL, 0)]
_DONE = {}


def _rehearse(case):
    if case not in _DONE:
        cmd = [sys.executable] + BENCH["command"][1:] + [
            "--workload", case[0], "--seed", str(2**31 + 29), "--seconds",
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
    # embed, 3 x (2 norms, mla), the dense ffn, 2 x (router, experts,
    # shared), the final norm and the head
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
    assert named & {"blk1_moe_experts", "blk2_moe_experts"}
    assert named & {"blk0_mla", "blk1_mla", "blk2_mla"}
    counters = _said(proc, "program_spans")["counters"]
    assert counters["runtime.recomputed_blocks"] % 3 == 0
    assert counters["kernels.gmm.ragged_dot"] >= 2
    assert counters["moe.experts_held"] == 4
    assert counters["moe.rows_capacity"] == 96   # 2 x 64 x 3 x 4/16


def test_configuration_file_keeps_the_published_sizes():
    published = dict(
        hidden_size=2048, intermediate_size=11264, kv_lora_rank=512,
        moe_intermediate_size=1408, num_attention_heads=16,
        num_key_value_heads=16, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, num_experts_per_tok=6, n_shared_experts=2,
        first_k_dense_replace=1, routed_scaling_factor=2.446,
        rope_theta=50000, rms_norm_eps=1e-05, max_position_embeddings=8192,
        num_hidden_layers=27, q_lora_rank=None, scoring_func="sigmoid",
        topk_method="noaux_tc", n_group=1, topk_group=1, moe_layer_freq=1,
        norm_topk_prob=True, tie_word_embeddings=False, hidden_act="silu")
    for key, value in published.items():
        assert CONFIG[key] == value, key
    assert CONFIG["published"] == {"num_hidden_layers": 27,
                                   "n_routed_experts": 64,
                                   "vocab_size": 163840}
    assert CONFIG["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (CONFIG["num_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (6, 8, 20480)
    assert CONFIG["router_outputs"] == 64
    assert CONFIG["experts_held"] == [0, 8]
    assert "eight chips share each layer" in CONFIG["deployment"]
    # the floors of a cut: a whole period and four layers after the dense
    # one, eight experts, an eighth of the vocabulary
    assert CONFIG["num_layers"] - CONFIG["first_k_dense_replace"] >= 4
    assert CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= CONFIG["published"]["vocab_size"]
    for key in ("optimizer", "bias_update_rate", "seq_aux", "rope_pairing",
                "rows_capacity_factor", "weights", "output_head"):
        assert CONFIG["assumed"][key]
    assert MIX["batch"] * MIX["seq_length"] == 16384
    assert MIX["reference_chunk"] == 1


def test_parameters_are_the_count_the_issue_reckons():
    """668 890 112 parameters at published widths, from the operators'
    own ``param_bytes`` (no array is made)."""
    import jax

    from flexflow_tpu.machine import MachineModel
    from flexflow_tpu.models.latent_moe import LatentMoEConfig, LatentMoELM

    ff = LatentMoELM(LatentMoEConfig.from_config(
        CONFIG, batch_size=2, seq_length=8192),
        MachineModel(jax.devices()[:1]))
    by_op = {op.name: op.param_bytes() // 4 for op in ff.layers}
    assert by_op["blk0_mla"] == 13_763_072
    assert by_op["blk0_ffn"] == 69_206_016
    assert by_op["blk1_moe_shared"] == 17_301_504
    assert by_op["blk1_moe_router"] == 131_072
    assert by_op["blk1_moe_experts"] == 8 * 8_650_752
    assert sum(by_op.values()) == 668_890_112
    assert ff.layers[[op.name for op in ff.layers].index(
        "blk1_moe_experts")].rows_capacity == 24576


def test_flops_and_kernel_work_from_shapes():
    from benchmarks.flops import moonlight_16b_a3b as flops

    per_token = flops.train_flops_per_item(CONFIG, MIX)
    assert per_token / 3e6 == pytest.approx(878.34, abs=0.01)
    assert per_token * 16384 == pytest.approx(43.17e12, rel=1e-3)
    assert flops.held_experts_per_token(CONFIG) == 0.75
    work = flops.kernel_work(CONFIG, MIX)
    # six half-square products a head: three 192 wide, three 128 wide
    assert work["ff_flash_"]["flops"] == 6 * 2 * 16 * 2.0 * 8192 ** 2 * (
        3 * 192 + 3 * 128) / 2
    assert work["ff_flash_"]["bytes"] == 6 * 2 * 16 * 8192 * 2 * (
        6 * 192 + 6 * 128)
    assert work["grouped_mm"]["flops"] == 5 * 9 * 2.0 * 12288 * 2048 * 1408


def test_the_reference_shares_no_code_with_the_program():
    path = os.path.join(ROOT, "benchmarks", "reference",
                        "moonlight_16b_a3b.py")
    with open(path) as f:
        text = f.read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", text, re.M)
    assert imports and all(m.split(".")[0] == "jax" for m in imports)


def _reader(name):
    from benchmarks import harness

    return harness.load_by_name(os.path.join(ROOT, "benchmarks",
                                             "layer_metrics"), name)


def test_the_new_readers_read_operator_seconds():
    from benchmarks.flops import moonlight_16b_a3b as flops

    by_op = {("blk0_mla", "forward"): 0.4, ("blk1_mla", "backward"): 1.2,
             ("blk1_moe_router", "forward"): 0.1,
             ("blk1_moe_experts", "forward"): 0.2,
             ("blk2_moe_experts", "backward"): 0.6,
             ("blk1_moe_shared", "backward"): 0.3,
             ("blk1_moe_sum", "forward"): 9.0, ("lm_head", "forward"): 9.0}
    facts = {"fences": [(0.0, 0), (1.0, 4)], "traced_steps": 4,
             "config": CONFIG, "mix": MIX, "flops": flops,
             "peaks": {"bf16_flops_per_s": 197e12,
                       "hbm_bytes_per_s": 819e9},
             "program_trace": {"on_chip": True, "steps": 4,
                               "trace": {"operator_s": by_op}}}
    assert _reader("ops.mla_ms_per_step").read(facts) \
        == pytest.approx(400.0)
    assert _reader("ops.moe_ms_per_step").read(facts) \
        == pytest.approx(300.0)
    floor = flops.kernel_work(CONFIG, MIX)["grouped_mm"]["flops"] / 197e12
    assert _reader("kernels.grouped_mm_roofline").read(facts) \
        == pytest.approx(100 * floor / 0.2)
    # a program without such operators (the parent), a refused table and
    # a CPU rehearsal leave the metrics out and raise nothing
    for prog in ({"on_chip": True, "steps": 4,
                  "trace": {"operator_s": {("lm_head", "forward"): 1.0}}},
                 {"on_chip": True, "steps": 4, "trace": {}},
                 {"on_chip": False, "steps": 4,
                  "trace": {"operator_s": by_op}}, None):
        for name in ("ops.mla_ms_per_step", "ops.moe_ms_per_step",
                     "kernels.grouped_mm_roofline"):
            assert _reader(name).read(dict(facts, program_trace=prog)) \
                is None
