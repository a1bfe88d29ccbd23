"""The ``granite_4_0_h_micro`` configuration and the cell of PR 32: the
cell's CPU rehearsal as the driver runs the benchmark (the model's loss
and every operator's applied gradient against the plain reference through
``compare.train_step``, both kinds of line), the configuration file
against every published key, the FLOPs and kernel work from shapes, and
the two readers."""

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
                       "granite_4_0_h_micro.json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "train_1chip_b2_s8192_ref2.json")) as _f:
    MIX = json.load(_f)

CELL = "granite_4_0_h_micro.train_1chip_b2_s8192_ref2"
CASES = [(CELL, 0), (CELL, 1)]
_DONE = {}

# the catalog row's ``config`` (huggingface.co/ibm-granite/
# granite-4.0-h-micro, config.json), layer_types by its period
PUBLISHED = dict(
    num_hidden_layers=40,
    attention_bias=False, attention_multiplier=0.015625,
    embedding_multiplier=12, hidden_act="silu", hidden_size=2048,
    intermediate_size=8192, logits_scaling=8, mamba_chunk_size=256,
    mamba_conv_bias=True, mamba_d_conv=4, mamba_d_head=64,
    mamba_d_state=128, mamba_expand=2, mamba_n_groups=1, mamba_n_heads=64,
    mamba_proj_bias=False, max_position_embeddings=131072,
    model_type="granitemoehybrid", normalization_function="rmsnorm",
    num_attention_heads=32, num_experts_per_tok=0, num_key_value_heads=8,
    num_local_experts=0, position_embedding_type="nope",
    residual_multiplier=0.22, rms_norm_eps=1e-05, rope_scaling=None,
    rope_theta=10000, shared_intermediate_size=8192,
    tie_word_embeddings=True, vocab_size=100352)
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


def _rehearse(case):
    if case not in _DONE:
        cmd = [sys.executable] + BENCH["command"][1:] + [
            "--workload", case[0], "--seed", str(2**31 + 32), "--seconds",
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
    # the embedding (with the tied head's part), 3 x 2 norms, 2 mixers of
    # three operators, one attention, 3 feed-forwards, the final norm
    assert c["ops"] == 1 + 3 * 2 + 2 * 3 + 1 + 3 + 1
    assert c["ops_under_rounding_floor"] == []
    assert c["loss_rel_err"] <= tol["loss_rel"]
    assert c["grad_rel_l2"] <= tol["grad_rel_l2"]
    assert c["worst_op_grad_rel_l2"] <= tol["op_grad_rel_l2"]
    assert all(raw <= 2e-3 for _, _, raw in c["worst_ops"])
    assert notes["last_loss"] == notes["last_loss"]      # not NaN


def test_traced_rehearsal_names_the_operators_and_counts_the_mechanisms():
    proc = _rehearse((CELL, 1))
    ops = _said(proc, "operators")
    assert not ops.get("refused") and ops["attributed_share"] > 0.8
    named = {k.split("|")[0] for k, _ in ops["top"]}
    assert named & {"blk0_ssm_scan", "blk2_ssm_scan"}
    assert named & {"blk0_ssm_in", "blk2_ssm_in"}
    counters = _said(proc, "program_spans")["counters"]
    assert counters["runtime.recomputed_blocks"] % 3 == 0
    assert counters["ssm.layers"] == 2
    assert counters["ssm.chunk"] == 12          # does not divide 32 steps
    assert counters["ssm.chunks_per_sequence"] == 3
    assert counters["kernels.ssd.xla_chunked.12x4x16"] >= 2
    assert counters["attn.kv_groups"] == 2
    assert not any(k.startswith("moe.") for k in counters)


def test_configuration_file_keeps_every_published_key():
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key
    assert CONFIG["layer_types"] == PERIOD
    # the cut in depth: one period of the pattern, under the key
    # moonlight_16b_a3b uses (the schema refuses "hidden" in a reduced key)
    assert CONFIG["num_layers"] == 10 == len(CONFIG["layer_types"])
    assert CONFIG["published"] == {"num_hidden_layers": 40}
    assert CONFIG["reduced"] == ["num_layers", "layer_types"]
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "granite_4_0_h_micro")
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert "four pipeline stages of ten layers" in CONFIG["deployment"]
    assert (CONFIG["compute_dtype"], CONFIG["param_dtype"]) \
        == ("bfloat16", "float32")
    assert CONFIG["optimizer"]["kind"] == "sgd"
    # the floors of a cut: a whole period, the whole vocabulary
    assert CONFIG["layer_types"].count("attention") == 1
    for key in ("optimizer", "weights", "embedding_std", "dropout",
                "recomputation"):
        assert CONFIG["assumed"][key]
    tol = CONFIG["tolerance"]
    assert tol["why"] and 0 < tol["grad_rel_l2"] < tol["op_grad_rel_l2"] < 1
    assert (MIX["batch"], MIX["seq_length"]) == (2, 8192)
    assert (MIX["warmup_steps"], MIX["fence_every"], MIX["prefetch_depth"],
            MIX["trace_seconds"], MIX["reference_chunk"]) == (2, 2, 2, 5, 2)
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == MIX["chips"] == 1
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"ops.ssm_ms_per_step", "kernels.ssd_scan_roofline",
            "kernels.flash_attn_roofline", "ops.mfu",
            "ops.attributed_share"} <= listed
    assert not listed & {"ops.step_roofline", "ops.mla_ms_per_step",
                         "ops.moe_ms_per_step",
                         "kernels.grouped_mm_roofline", "plan.sim_drift"}


def test_flops_and_kernel_work_from_shapes():
    from benchmarks.flops import granite_4_0_h_micro as flops

    per_token = flops.train_flops_per_item(CONFIG, MIX)
    assert per_token / 3e6 == pytest.approx(1975.6, abs=0.05)
    assert per_token * 16384 == pytest.approx(97.1e12, rel=1e-3)
    assert flops.scan_flops_per_token(CONFIG) == 4_259_840
    work = flops.kernel_work(CONFIG, MIX)
    # six half-square products a query head at width 64; q, o, do, dq by
    # 32 heads and k, v, dk, dv by 8
    assert work["ff_flash_"]["flops"] == 2 * 32 * 2.0 * 8192 ** 2 * 6 * 64 / 2
    assert work["ff_flash_"]["bytes"] == 2 * 8192 * 64 * 2 * (6 * 32 + 6 * 8)
    assert work["ssd_scan"]["flops"] == 9 * 16384 * 3 * 4_259_840
    assert work["ssd_scan"]["flops"] == pytest.approx(1.9e12, rel=0.02)
    assert work["ssd_scan"]["flops"] / 197e12 \
        > work["ssd_scan"]["bytes"] / 819e9 > 0.0075
    # a Mamba layer 156.6 M a token forward, its mixer 56.0 M of them
    one = dict(CONFIG, layer_types=["mamba"])
    none = dict(CONFIG, layer_types=[])
    layer = (flops.train_flops_per_item(one, MIX)
             - flops.train_flops_per_item(none, MIX)) / 3e6
    assert layer == pytest.approx(156.6, abs=0.05)
    assert layer - 6 * 2048 * 8192 / 1e6 == pytest.approx(56.0, abs=0.1)
    assert flops.train_flops_per_item(none, MIX) / 3e6 \
        == pytest.approx(411.0, abs=0.05)


def test_the_reference_shares_no_code_with_the_program():
    path = os.path.join(ROOT, "benchmarks", "reference",
                        "granite_4_0_h_micro.py")
    with open(path) as f:
        text = f.read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", text, re.M)
    assert imports and all(m.split(".")[0] == "jax" for m in imports)
    # the recurrence over time steps, not the chunked form
    assert "lax.scan(step" in text and "cumsum" not in text


def _reader(name):
    from benchmarks import harness

    return harness.load_by_name(os.path.join(ROOT, "benchmarks",
                                             "layer_metrics"), name)


def test_the_new_readers_read_operator_seconds():
    from benchmarks.flops import granite_4_0_h_micro as flops

    by_op = {("blk0_ssm_in", "forward"): 0.4,
             ("blk0_ssm_scan", "forward"): 0.2,
             ("blk4_ssm_scan", "backward"): 0.6,
             ("blk9_ssm_out", "backward"): 0.8,
             ("blk5_attn", "backward"): 9.0, ("blk0_ffn", "forward"): 9.0,
             ("lm_head", "forward"): 9.0}
    facts = {"fences": [(0.0, 0), (1.0, 4)], "traced_steps": 4,
             "config": CONFIG, "mix": MIX, "flops": flops,
             "peaks": {"bf16_flops_per_s": 197e12,
                       "hbm_bytes_per_s": 819e9},
             "program_trace": {"on_chip": True, "steps": 4,
                               "trace": {"operator_s": by_op}}}
    assert _reader("ops.ssm_ms_per_step").read(facts) \
        == pytest.approx(500.0)
    floor = flops.kernel_work(CONFIG, MIX)["ssd_scan"]["flops"] / 197e12
    assert _reader("kernels.ssd_scan_roofline").read(facts) \
        == pytest.approx(100 * floor / 0.2)
    # a program without such operators (the parent), a refused table and
    # a CPU rehearsal leave the metrics out and raise nothing
    for prog in ({"on_chip": True, "steps": 4,
                  "trace": {"operator_s": {("lm_head", "forward"): 1.0}}},
                 {"on_chip": True, "steps": 4, "trace": {}},
                 {"on_chip": False, "steps": 4,
                  "trace": {"operator_s": by_op}}, None):
        for name in ("ops.ssm_ms_per_step", "kernels.ssd_scan_roofline"):
            assert _reader(name).read(dict(facts, program_trace=prog)) \
                is None
