"""``benchmarks/program_trace.py`` (PR 26) against numbers worked out by
hand on a hand-made two-chip trace with ``ff:`` spans and a hand-made
operator table; the seven per-layer entries that read it; and one CPU
rehearsal of a traced cell, which must print both new lines before the
result line and report the metrics a CPU can."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks import program_trace as pt

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NS = 1e-9
K = 1000

CELLS = ["inception_v3.train_1chip_b256", "gpt2_small.train_1chip_b16_s1024",
         "alexnet_owt.train_searched_4chip_b8192"]
NEW = {"entry.state_init_s": CELLS, "entry.trace_lower_s": CELLS,
       "ops.forward_ms_per_step": CELLS, "ops.backward_ms_per_step": CELLS,
       "ops.update_ms_per_step": CELLS, "ops.attributed_share": CELLS,
       "executor.regrid_ms_per_step": CELLS[2:]}

# --- the hand-made trace (nanoseconds) -------------------------------------
# host:   bench:trace_window [0, 1000k)   bench:train_step [0, 50k)
#         ff:runtime.prefetch_wait [10k, 40k)   bench:fence [50k, 828k)
# chip 0, XLA Ops:  fusion.1 [100k,300k)  while.1 [300k,600k) holding
#         fusion.2 [320k,500k)   fusion.3 [600k,650k)
#         all-reduce.1 [700k,780k)   all-to-all.1 [800k,820k)
#         Async XLA Ops: all-gather-start.1 [640k,700k)
#         XLA Modules:   jit_ff_train_step(7) [90k,830k)
# chip 1: the same, but fusion.3 runs [600k,700k)
TABLE = {"fusion.1": ("conv1", "forward"), "fusion.2": ("conv1", "backward"),
         "while.1": ("", "other"), "fusion.3": ("ff_update", "update"),
         "all-reduce.1": ("fc", "backward"),
         "all-to-all.1": ("ff_regrid.fc.0", "regrid"),
         "all-gather-start.1": ("fc", "forward")}


def _ev(plane, line, name, lo, hi, **kw):
    return dict(plane=plane, line=line, name=name, start_ns=float(lo * K),
                dur_ns=float((hi - lo) * K), **kw)


def _trace():
    host = "/host:CPU"
    ev = [_ev(host, "python", "bench:trace_window", 0, 1000),
          _ev(host, "python", "bench:train_step", 0, 50),
          _ev(host, "python", "ff:runtime.prefetch_wait", 10, 40,
              args={"batch": 3}),
          _ev(host, "python", "bench:fence", 50, 828)]
    for chip, update_end in ((0, 650), (1, 700)):
        d = f"/device:TPU:{chip}"
        ev += [
            _ev(d, "XLA Ops", "%fusion.1 = bf16[8]{0} fusion(%p), "
                "kind=kOutput, calls=%fc", 100, 300),
            _ev(d, "XLA Ops", "%while.1 = (s32[]) while(%t), body=%b",
                300, 600),
            _ev(d, "XLA Ops", "%fusion.2 = bf16[8]{0} fusion(%q), "
                "kind=kLoop, calls=%fc.1", 320, 500),
            _ev(d, "XLA Ops", "%fusion.3 = f32[8]{0} fusion(%w, %g), "
                "kind=kLoop, calls=%fc.2", 600, update_end),
            _ev(d, "XLA Ops", "%all-reduce.1 = f32[8]{0} all-reduce(%g), "
                "replica_groups={}", 700, 780),
            _ev(d, "XLA Ops", "%all-to-all.1 = f32[8]{0} all-to-all(%x)",
                800, 820),
            _ev(d, "Async XLA Ops", "%all-gather-start.1 = (f32[2], f32[8])"
                " all-gather-start(%y)", 640, 700),
            _ev(d, "XLA Modules", "jit_ff_train_step(7)", 90, 830)]
    return ev


@pytest.fixture(scope="module")
def reduced():
    return pt.reduce_program(_trace(), TABLE)


@pytest.mark.parametrize("pas,ns", [
    ("forward", 200 * K), ("backward", (180 + 80) * K),
    ("update", (50 + 100) / 2 * K), ("regrid", 20 * K),
    ("other", (300 - 180) * K)])        # the while's own time
def test_seconds_by_pass(reduced, pas, ns):
    assert reduced["pass_s"][pas] == pytest.approx(ns * NS)


def test_passes_sum_to_the_operations_busy_time(reduced):
    # chip 0: [100k,650k) + [700k,780k) + [800k,820k); chip 1: 50k more
    assert reduced["ops_busy_s"] == pytest.approx((650 + 700) / 2 * K * NS)
    assert sum(reduced["pass_s"].values()) == pytest.approx(
        reduced["ops_busy_s"])
    # with the async all-gather in flight both chips are busy [100k,780k)
    assert reduced["busy_s"] == pytest.approx(700 * K * NS)
    assert reduced["devices"] == 2
    assert reduced["window_s"] == pytest.approx(1000 * K * NS)


@pytest.mark.parametrize("key,ns", [
    (("conv1", "forward"), 200 * K), (("conv1", "backward"), 180 * K),
    (("fc", "backward"), 80 * K), (("ff_update", "update"), 75 * K),
    (("ff_regrid.fc.0", "regrid"), 20 * K), (("", "other"), 120 * K)])
def test_seconds_by_operator_and_pass(reduced, key, ns):
    assert reduced["operator_s"][key] == pytest.approx(ns * NS)
    assert "fusion.2" in reduced["instruction_s"]
    assert reduced["instruction_s"]["fusion.2"]["operator"] == "conv1"


@pytest.mark.parametrize("operator,ns", [
    ("fc", (80 + 60) * K),           # the all-reduce and the all-gather
    ("ff_regrid.fc.0", 20 * K)])
def test_collectives_by_operator(reduced, operator, ns):
    assert reduced["collective_s"][operator] == pytest.approx(ns * NS)
    assert set(reduced["collective_s"]) == {"fc", "ff_regrid.fc.0"}


@pytest.mark.parametrize("span,ns", [
    # [0,10k) and [40k,50k) of the first gap; the ff: span inside it is
    # the innermost where it runs
    ("bench:train_step", 20 * K),
    ("ff:runtime.prefetch_wait", 30 * K),
    # [50k,100k), [780k,800k), [820k,828k)
    ("bench:fence", (50 + 20 + 8) * K),
    ("outside_bench_spans", (1000 - 828) * K)])
def test_gaps_go_to_the_innermost_of_bench_and_ff_spans(reduced, span, ns):
    assert reduced["idle_gaps_s"][span] == pytest.approx(ns * NS)
    assert sum(reduced["idle_gaps_s"].values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])


def test_clock_lead_lies_between_the_fence_and_the_dispatch(reduced):
    # least: the module reads an end at 830k, the fence that waited for it
    # 828k.  Most: it reads a start at 90k, the step was dispatched at 0
    assert reduced["clock_lead_ms"] == pytest.approx([0.002, 0.090])
    no_fence = [e for e in _trace() if e["name"] != "bench:fence"]
    assert pt.clock_lead_ns(no_fence) is None


def test_clock_lead_where_the_device_clock_lags():
    """Two steps, two fences, the device's clock 3k behind the host's:
    the second program reads a start before the first fence's end, and
    is still the second step's, not the one the first fence waited for
    (found on the four-chip host, PR 26: the old pairing read 78 ms)."""
    d, h = "/device:TPU:0", "/host:CPU"
    ev = [_ev(h, "python", "bench:train_step", 0, 10),
          _ev(h, "python", "bench:fence", 10, 105),      # woke 2k late
          _ev(h, "python", "bench:train_step", 106, 116),
          _ev(h, "python", "bench:fence", 116, 212),
          # true times [3,103) and [107,209), read 3k early
          _ev(d, "XLA Modules", "jit_ff_train_step(7)", 0, 100),
          _ev(d, "XLA Modules", "jit_ff_train_step(7)", 104, 206)]
    least, most = pt.clock_lead_ns(ev)
    assert least == pytest.approx(-5 * K)     # lag 3k and 2k to wake
    assert most == pytest.approx(-2 * K)      # lag 3k less 1k to launch
    assert least <= -3 * K <= most


def test_an_instruction_the_table_lacks_refuses_the_operator_part():
    said = []
    table = {k: v for k, v in TABLE.items() if k != "fusion.2"}
    red = pt.reduce_program(_trace(), table, said.append)
    assert "pass_s" not in red and "operator_s" not in red
    assert len(said) == 1 and "refused" in said[0] and "fusion.2" in said[0]
    # what needs no table is still there
    assert red["busy_s"] == pytest.approx(700 * K * NS)
    assert red["idle_gaps_s"]["ff:runtime.prefetch_wait"] == \
        pytest.approx(30 * K * NS)


def test_without_a_table_or_without_a_device_there_is_no_operator_part():
    red = pt.reduce_program(_trace(), None)
    assert "pass_s" not in red and red["clock_lead_ms"] is not None
    host_only = [e for e in _trace() if e["plane"] == "/host:CPU"]
    assert pt.reduce_program(host_only, TABLE) is None


def test_program_spans_reads_what_ran_before_the_window_opened():
    import time

    from flexflow_tpu import obs

    obs.reset()
    try:
        with obs.span("ff:entry.abstract_state"):
            with obs.span("ff:entry.init"):
                time.sleep(0.01)
        obs.count("compile.trace_s", 1.5)
        obs.count("compile.lower_s", 0.5)
        time.sleep(0.3)
        t_open = time.perf_counter()
        with obs.span("ff:entry.init"):       # after the window opened
            pass
        obs.count("compile.trace_s", 4.0)
        prog = pt.program_spans({"fences": [(t_open, 0)]})
    finally:
        obs.reset()
    before = prog["entry_before_open_s"]
    assert set(before) == set(pt.ENTRY_SPANS)
    assert before["ff:entry.init"] >= 0.01
    # self time: the outer span is charged only what the inner leaves
    assert before["ff:entry.abstract_state"] < 0.005
    assert before["ff:entry.regrid_plan"] == 0
    assert prog["spans"]["ff:entry.init"]["count"] == 2
    assert prog["compile_before_open_s"]["compile.trace_s"] == 1.5
    assert prog["compile_before_open_s"]["compile.lower_s"] == 0.5
    assert prog["counters"]["compile.trace_s"] == 5.5


def _reader(name):
    path = os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _facts(reduced, on_chip=True):
    return {"program_trace": {
        "trace": reduced, "on_chip": on_chip, "steps": 2,
        "entry_before_open_s": {"ff:entry.init": 1.25,
                                "ff:entry.opt_state": 0.25},
        "compile_before_open_s": {"compile.trace_s": 2.0,
                                  "compile.lower_s": 0.5,
                                  "compile.backend_s": 9.0}}}


@pytest.mark.parametrize("name,want", [
    ("entry.state_init_s", 1.5), ("entry.trace_lower_s", 2.5),
    ("ops.forward_ms_per_step", 0.2 / 2),
    ("ops.backward_ms_per_step", 0.26 / 2),
    ("ops.update_ms_per_step", 0.075 / 2),
    ("executor.regrid_ms_per_step", 0.02 / 2),
    ("ops.attributed_share", 100 * (675 - 120) / 675)])
def test_readers_on_the_hand_made_trace(reduced, name, want):
    assert _reader(name).read(_facts(reduced)) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(NEW))
def test_readers_leave_a_device_metric_out_off_the_chip(reduced, name):
    value = _reader(name).read(_facts(reduced, on_chip=False))
    if name.startswith("entry."):
        assert value is not None        # the host's own spans and counters
    else:
        assert value is None            # a CPU run is no device time
    refused = _facts(pt.reduce_program(_trace(), None))
    if not name.startswith("entry."):
        assert _reader(name).read(refused) is None
    assert _reader(name).read({}) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_entry_follows_the_schemas_rules(name):
    """What test_benchmark_schema.py holds every entry to, and this PR's
    own: an explicit ``workloads`` list, appended after PR 24's
    entries."""
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["workloads"] == NEW[name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    mod = _reader(name)
    assert mod.METRIC == {k: v for k, v in entry.items()
                          if k != "workloads"}
    assert mod.__doc__
    layers = {m["layer"] for m in BENCH["per_layer"]
              if m["name"] not in NEW}
    assert entry["layer"] in layers         # a layer PERF.md already has
    moved = [m for m in BENCH["end_to_end"] if m["name"] == entry["moves"]]
    assert moved and set(entry["workloads"]) <= set(
        moved[0].get("workloads", CELLS))
    index = [m["name"] for m in BENCH["per_layer"]].index(name)
    assert index >= len(BENCH["per_layer"]) - len(NEW)


# --- one traced rehearsal, as the driver runs the benchmark ----------------

@pytest.fixture(scope="module")
def rehearsal():
    cmd = [sys.executable] + BENCH["command"][1:] + [
        "--workload", "gpt2_small.train_1chip_b16_s1024", "--seed",
        str(2**31 + 26), "--seconds", "1", "--trace", "1",
        "--cpu-rehearsal"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 3, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()


def _said(lines, what):
    (line,) = [l for l in lines if l.startswith(f"benchmark: {what} ")]
    return lines.index(line), json.loads(line.split(" ", 2)[2])


def test_rehearsal_prints_both_lines_before_the_result_line(rehearsal):
    at_ops, ops = _said(rehearsal, "operators")
    at_spans, spans = _said(rehearsal, "program_spans")
    assert at_ops < at_spans < len(rehearsal) - 1
    assert json.loads(rehearsal[-1])["correct"] is False
    assert ops["on_chip"] is False and ops["traced_steps"] >= 1
    # the table of the step that ran names every instruction of the trace
    assert "refused" not in ops
    per_step = ops["ms_per_step"]
    assert set(per_step) == set(pt.PASSES)
    assert per_step["forward"] > 0 and per_step["backward"] > 0
    assert per_step["update"] > 0 and per_step["regrid"] == 0
    assert ops["attributed_share"] > 0.5
    assert any(k.endswith("|backward") for k, _ in ops["top"])
    assert len(ops["instructions"][0]) == 4


def test_rehearsal_reports_the_programs_spans_and_counters(rehearsal):
    _, spans = _said(rehearsal, "program_spans")
    for name in ("ff:entry.abstract_state", "ff:entry.init",
                 "ff:entry.opt_state", "ff:entry.graph_plan",
                 "ff:runtime.prefetch_put", "ff:runtime.prefetch_wait"):
        count, total, own = spans["spans"][name]
        assert count >= 1 and total >= own >= 0
    assert spans["counters"]["compile.trace_s"] > 0
    assert spans["counters"]["compile.backend_s"] > 0
    before = spans["compile_before_open_s"]
    assert 0 < before["compile.trace_s"] <= spans["counters"][
        "compile.trace_s"]
    assert spans["entry_before_open_s"]["ff:entry.init"] > 0
    assert isinstance(spans["idle_gaps_ms_per_step"], list)


def test_rehearsal_result_line_holds_the_metrics_a_cpu_can_read(rehearsal):
    metrics = json.loads(rehearsal[-1])["metrics"]
    _, spans = _said(rehearsal, "program_spans")
    assert metrics["entry.state_init_s"]["value"] == pytest.approx(
        sum(spans["entry_before_open_s"].values()), abs=1e-5)
    assert metrics["entry.trace_lower_s"]["value"] == pytest.approx(
        spans["compile_before_open_s"]["compile.trace_s"]
        + spans["compile_before_open_s"]["compile.lower_s"], abs=1e-5)
    assert metrics["entry.state_init_s"]["unit"] == "s"
    # device time comes from a chip run alone
    assert not [m for m in metrics if m.startswith(("ops.", "executor."))]
