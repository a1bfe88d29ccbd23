"""A CPU rehearsal of each driver at a tiny size, as the driver runs the
benchmark: a process of its own, from the command in BENCHMARK.json.  It
must reach the last line, the line must hold exactly the contract's keys,
``correct`` must be false and the exit code non-zero.  The four-chip cell
runs on four virtual CPU devices: it builds its mesh, searches and loads
a plan and passes the plan checker (a plan that fails it exits before any
line is printed)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

# one cell per builder, both kinds of line, and the four-chip path;
# Inception at 299x299 compiles for most of a minute on the CPU and rides
# the same driver and builder as AlexNet, so its rehearsal stays a manual
# command.
CASES = [("gpt2_small.train_1chip_b16_s1024", 0),
         ("gpt2_small.train_1chip_b16_s1024", 1),
         ("alexnet_owt.train_searched_4chip_b8192", 1)]


def _run(workload, trace, *extra, env=None):
    cmd = [sys.executable] + BENCH["command"][1:] + [
        "--workload", workload, "--seed", str(2**31 + 17), "--seconds", "1",
        "--trace", str(trace), *extra]
    e = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="whatever")
    e.pop("XLA_FLAGS", None)          # the harness asks for its devices
    e.update(env or {})
    return subprocess.run(cmd, cwd=ROOT, env=e, capture_output=True,
                          text=True, timeout=600)


_DONE = {}


def _rehearse(case):
    """One process per case, shared by the tests of this file."""
    if case not in _DONE:
        _DONE[case] = _run(case[0], case[1], "--cpu-rehearsal")
    return case[0], case[1], _DONE[case]


@pytest.fixture(params=CASES, ids=lambda c: f"{c[0]}-trace{c[1]}")
def rehearsal(request):
    return _rehearse(request.param)


def test_rehearsal_reaches_the_last_line_and_fails(rehearsal):
    workload, trace, proc = rehearsal
    assert proc.returncode not in (0, 1, 2), proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}       # breakdown only with a device trace
    assert line["correct"] is False
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}


def test_rehearsal_reports_the_cells_metrics(rehearsal):
    workload, trace, proc = rehearsal
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    kind = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in BENCH[kind]
                if workload in m.get("workloads", [workload])}
    assert line["metrics"], proc.stdout[-2000:]
    for name, m in line["metrics"].items():
        assert declared[name] == m["unit"]
        assert isinstance(m["value"], float)
    if not trace:                  # every end-to-end metric of the cell
        assert set(line["metrics"]) == set(declared)


def test_rehearsal_prints_phases_and_compile_counts_before(rehearsal):
    workload, trace, proc = rehearsal
    lines = proc.stdout.strip().splitlines()
    phases = json.loads(next(l for l in lines if l.startswith(
        "benchmark: phases ")).split(" ", 2)[2])
    assert {"import", "device_init", "plan", "build_init", "warmup",
            "window", "correctness", "trace"} <= set(phases)
    compiles = json.loads(next(l for l in lines if l.startswith(
        "benchmark: compile ")).split(" ", 2)[2])
    assert compiles["in_window"]["compiles"] == 0
    notes = json.loads(next(l for l in lines if l.startswith(
        "benchmark: notes ")).split(" ", 2)[2])
    assert "correctness" in notes


def test_four_chip_rehearsal_used_four_devices_and_a_searched_plan():
    workload, trace, proc = _rehearse(CASES[-1])
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert "plan.sim_drift" in line["metrics"]   # the artifact's prediction
    plan = os.path.join(ROOT, ".bench_cache", "plans",
                        workload + ".rehearsal.json")
    with open(plan) as f:
        body = json.load(f)
    assert body["__predicted__"]["devices"] == 4
    assert all(len(v["devices"]) == 4 for k, v in body.items()
               if k != "__predicted__")


def test_off_the_tpu_without_the_argument_there_is_no_result():
    proc = _run("gpt2_small.train_1chip_b16_s1024", 0)
    assert proc.returncode != 0
    assert "not a TPU" in proc.stderr
    assert not [l for l in proc.stdout.splitlines() if l.startswith("{")]


def test_unknown_workload_is_refused():
    proc = _run("no_such.cell", 0, "--cpu-rehearsal")
    assert proc.returncode != 0 and "no workload" in proc.stderr


def test_bare_directory_prints_no_result(tmp_path):
    """BENCHMARK.json and the files under ``paths`` alone: no program to
    measure, so no result and a non-zero exit."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cmd = [sys.executable] + BENCH["command"][1:] + [
        "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
        "--seconds", "1", "--trace", "0"]
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(cmd, cwd=tmp_path, env=dict(env,
                          JAX_PLATFORMS="cpu"), capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0
    assert not [l for l in proc.stdout.splitlines() if l.startswith("{")]
