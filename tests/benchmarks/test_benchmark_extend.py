"""A later PR adds a configuration, a traffic mix and a per-layer metric
as files of its own and entries in BENCHMARK.json, and edits no file that
is there.  Shown on a copy of the benchmark's directories: three new
files and three new entries, the new cell's name appended to the
``workloads`` of the metrics it shares with the cells that are there, and
the harness runs the new cell and reports the new metric and the old
ones."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

READER = '''"""Steps the window ran (a later PR's metric)."""

METRIC = {"name": "runtime.window_steps", "unit": "steps",
          "better": "higher", "source": "program_counter",
          "layer": "runtime", "moves": "train_items_per_s_per_chip"}


def read(facts):
    return float(facts["steps"]) if "steps" in facts else None
'''


@pytest.fixture(scope="module")
def extended(tmp_path_factory):
    root = tmp_path_factory.mktemp("extended")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = {os.path.join(d, f): os.path.getmtime(os.path.join(d, f))
              for d, _, fs in os.walk(root) for f in fs}
    bdir = root / "benchmarks"

    # 1. a configuration: its file of sizes, with its reference and its
    #    shape function beside it (here: GPT-2's own, at other sizes)
    with open(bdir / "configs" / "gpt2_small.json") as f:
        cfg = json.load(f)
    cfg.update(cfg.pop("rehearsal"))
    cfg.update(name="gpt2_nano", rehearsal={})
    (bdir / "configs" / "gpt2_nano.json").write_text(json.dumps(cfg))
    for sub in ("reference", "flops"):
        shutil.copy(bdir / sub / "gpt2_small.py", bdir / sub / "gpt2_nano.py")
    # 2. a traffic mix: a data file the general drivers read
    (bdir / "traffic" / "train_tiny.json").write_text(json.dumps({
        "name": "train_tiny", "kind": "train", "chips": 1, "batch": 2,
        "seq_length": 16, "warmup_steps": 2, "fence_every": 2,
        "reference_chunk": 1}))
    # 3. a per-layer metric: a reader of its own
    (bdir / "layer_metrics" / "runtime.window_steps.py").write_text(READER)

    bench["configs"].append({
        "name": "gpt2_nano", "source": cfg["source"],
        "file": "benchmarks/configs/gpt2_nano.json", "reduced": [],
        "why": "a later PR's configuration"})
    bench["workloads"].append({
        "name": "gpt2_nano.train_tiny", "config": "gpt2_nano",
        "traffic": "train_tiny", "chips": 1, "why": "a later PR's cell"})
    # the new cell joins every metric of the training cells by its name
    # in BENCHMARK.json: no reader is edited
    shared = bench["workloads"][0]["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if shared in m.get("workloads", []):
            m["workloads"].append("gpt2_nano.train_tiny")
    bench["per_layer"].append({
        "name": "runtime.window_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "runtime",
        "moves": "train_items_per_s_per_chip",
        "workloads": ["gpt2_nano.train_tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = {p: os.path.getmtime(p) for p in before}
    assert after == before            # no file that was there was touched
    return root


def _run(root, trace):
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--root", str(root), "--workload", "gpt2_nano.train_tiny",
           "--seed", "5", "--seconds", "1", "--trace", str(trace),
           "--cpu-rehearsal"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)


def test_new_configuration_and_mix_are_found_by_name(extended):
    proc = _run(extended, 0)
    assert proc.returncode == 3, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line["metrics"]) == {"train_items_per_s_per_chip", "setup_s"}
    notes = json.loads(next(l for l in proc.stdout.splitlines() if
                            l.startswith("benchmark: notes ")
                            ).split(" ", 2)[2])
    # the copy's reference ran, and agrees: the new cell would be correct
    assert notes["correctness"]["grad_rel_l2"] <= 2e-3
    assert not [l for l in proc.stdout.splitlines()
                if l.startswith("benchmark: problem")]


def test_new_per_layer_metric_is_read_by_its_own_file(extended):
    proc = _run(extended, 1)
    assert proc.returncode == 3, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["metrics"]["runtime.window_steps"]["unit"] == "steps"
    assert line["metrics"]["runtime.window_steps"]["value"] >= 2
    # the metrics that list other cells are not reported in this one
    assert "kernels.flash_attn_roofline" not in line["metrics"]
    assert "entry.compile_s" in line["metrics"]    # of every cell
    # the training cells' metrics that a CPU can read came with the name
    assert line["metrics"]["runtime.step_ms_p50"]["value"] > 0
    assert "runtime.input_stall_share" in line["metrics"]


def test_new_cell_inherits_the_training_cells_device_metrics(extended):
    """What the CPU cannot read (a device trace, the chip's peaks) is
    handed to the harness's own reading of the new cell's metrics: the
    recorded one-chip trace, the v5e's peaks and two fences."""
    sys.path.insert(0, ROOT)
    from benchmarks import harness, trace_reduce

    cell = harness.load_cell(str(extended), "gpt2_nano.train_tiny")
    names = {m["name"] for m in cell["per_layer"]}
    assert {"ops.mfu", "device.idle_share.train", "runtime.step_ms_p50",
            "kernels.pallas_ms_per_step", "runtime.window_steps"} <= names
    with open(os.path.join(ROOT, "tests", "benchmarks", "fixtures",
                           "recorded_one_chip_trace.json")) as f:
        trace = trace_reduce.reduce_events(json.load(f)["events"])
    facts = {"trace": trace, "peaks": harness.load_peaks("TPU v5 lite"),
             "fences": [(0.0, 0), (1.0, 3200)], "items_per_step": 32,
             "steps": 100, "traced_steps": 10, "chips": 1,
             "config": cell["config"], "mix": cell["mix"],
             "flops": harness.load_by_name(os.path.join(
                 cell["dir"], "flops"), "gpt2_nano")}
    got = harness.read_per_layer(cell, facts)
    assert 0 < got["ops.mfu"]["value"] < 100
    assert got["device.idle_share.train"]["value"] == pytest.approx(
        100 * (1 - trace["busy_s"] / trace["window_s"]))
    assert got["runtime.window_steps"]["value"] == 100.0
