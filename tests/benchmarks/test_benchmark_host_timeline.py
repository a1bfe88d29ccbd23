"""``benchmarks/host_timeline.py`` (PR 36) against numbers worked out by
hand on hand-made facts with a profiler pause inside the window; what its
four readers answer where there is nothing, or not everything, to read;
the four ``per_layer`` entries; and one traced CPU rehearsal, which must
print exactly one ``host_timeline`` line whose parts add up."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks import host_timeline as ht

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
METRICS = ("entry.step_build_s", "entry.trace_wall_s",
           "runtime.host_dispatch_share", "runtime.gc_pause_share")
CELLS = [w["name"] for w in BENCH["workloads"]]

# --- the hand-made run (seconds on perf_counter) ---------------------------
# first call      ff:entry.step_build [50, 62): trace 6, lower 2, and JAX's
#                 backend event 3.5, of which the cache's fetch took 3
# warm-up         ff:runtime.step n=1 [99.0, 99.5)
# interval 0      bench:train_step [100.0, 100.1) [100.5, 100.6)
#                 bench:fence [101, 102)
# the profiler stops: [102, 105), 3 s that fences[] has cut out
# interval 1      bench:train_step [105.0, 105.1) [105.5, 105.6)
#                 bench:fence [106, 107)
# after           ff:runtime.step n=7 [110.0, 110.2)   (the comparison)
# so the window is [100, 102) + [105, 107) = 4 s
ITEMS = 10


def _facts():
    return {"fences": [(100.0, 0), (102.0, 2 * ITEMS), (104.0, 4 * ITEMS)],
            "items_per_step": ITEMS,
            "spans": {"bench:train_step": [(100.0, 100.1), (100.5, 100.6),
                                           (105.0, 105.1), (105.5, 105.6)],
                      "bench:fence": [(101.0, 102.0), (106.0, 107.0)],
                      "bench:correctness": [(108.0, 120.0)]}}


def _rec(name, start, end, **args):
    return {"name": name, "start": start, "end": end, "parent": None,
            "self_s": end - start, "thread": 1, "args": args}


def _snap():
    steps = [(99.0, 99.5),                         # warm-up: before
             (100.0, 100.1), (100.5, 100.6),       # 0.2 s in interval 0
             (103.0, 104.0),                       # inside the pause: out
             (104.9, 105.1),                       # 0.1 s of it in interval 1
             (105.5, 105.6),                       # 0.1 s
             (110.0, 110.2)]                       # the comparison: after
    records = [_rec("ff:entry.step_build", 50.0, 62.0, ops=7, blocks=2,
                    trace_s=6.0, lower_s=2.0, backend_s=3.5,
                    cache_fetch_s=3.0)]
    records += [_rec("ff:runtime.step", a, b, n=i + 1)
                for i, (a, b) in enumerate(steps)]
    records += [_rec("ff:runtime.gc", 100.7, 100.9, generation=2,
                     collected=5),
                _rec("ff:runtime.prefetch_wait", 105.2, 105.21, batch=3)]
    history = {
        # (first moment, last moment, value) of each quarter second it moved
        "runtime.gc_s": [(90.0, 90.0, 1.0), (100.7, 100.9, 1.2),
                         (103.0, 103.2, 2.2), (106.0, 106.1, 2.3),
                         (120.0, 120.0, 5.0)],
        "compile.trace_wall_s": [(55.0, 56.0, 6.0), (130.0, 130.0, 9.0)],
        "compile.lower_wall_s": [(57.0, 58.0, 2.0)],
        "entry.trace_op_s.Conv2D": [(52.0, 52.0, 1.5)],
        "entry.trace_op_s.Linear": [(53.0, 53.0, 2.5), (131.0, 131.0, 3.0)],
        "entry.trace_block_s.0": [(53.0, 53.0, 0.75)],
        "entry.trace_block_s.1": [(54.0, 54.0, 1.25)],
        "kernels.traced.ff_flash_fwd": [(55.0, 55.0, 10)],
        "kernels.traced.ff_flash_win_fwd": [(55.0, 55.0, 4)],
        "kernels.traced.ff_ce_bwd": [(55.0, 55.0, 1)],
        "kernels.flash.pack1.split": [(55.0, 55.0, 3)],
        "kernels.flash.pack1.split.w512": [(55.0, 55.0, 2)],
        "kernels.ce.fwd.512x2048": [(55.0, 55.0, 1)],
        "kernels.ce.fused_bwd.1024x512": [(55.0, 55.0, 1)],
    }
    names = {r["name"] for r in records}
    return {"records": sorted(records, key=lambda r: r["start"]),
            "spans": {n: {"count": sum(r["name"] == n for r in records)}
                      for n in names},
            "counters": {k: h[-1][2] for k, h in history.items()},
            "counter_history": history, "dropped": {}}


def _with(monkeypatch, snap):
    from flexflow_tpu import obs

    monkeypatch.setattr(
        ht, "_snapshot", lambda: dict(snap, counter_at=obs.counter_at))


def _reader(name):
    path = os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_window_is_rebuilt_from_the_bench_records():
    assert ht.window_intervals(_facts()) == [(100.0, 102.0), (105.0, 107.0)]
    assert ht.window_intervals({}) == []


@pytest.mark.parametrize("name, want", [
    ("entry.step_build_s", 12.0),
    # trace 6 + lower 2 at the window's open (the 9 came later)
    ("entry.trace_wall_s", 8.0),
    # 0.1 + 0.1 in interval 0, 0.1 + 0.1 in interval 1 (the call that
    # began in the pause counts from the interval's start), over 4 s
    ("runtime.host_dispatch_share", 100.0 * 0.4 / 4.0),
    # 1.0 -> 2.3 over [100, 107) less the 1.0 gained in the pause
    ("runtime.gc_pause_share", 100.0 * 0.3 / 4.0),
])
def test_reader_gives_the_number_worked_out_by_hand(monkeypatch, capsys,
                                                    name, want):
    _with(monkeypatch, _snap())
    facts = _facts()
    assert _reader(name).read(facts) == pytest.approx(want)
    for other in METRICS:                    # computed once a run
        _reader(other).read(facts)
    (said,) = capsys.readouterr().out.splitlines()
    assert said.startswith("benchmark: host_timeline ")


def test_the_line_holds_the_split_the_rankings_and_the_longest_events(
        monkeypatch):
    _with(monkeypatch, _snap())
    said = []
    host = ht.host_facts(_facts(), say=said.append)
    (text,) = said
    line = json.loads(text.split(" ", 2)[2])
    assert line == json.loads(json.dumps(host["line"]))
    build = line["step_build"]
    # the fetch lies inside the backend event: 0.5 s compiled
    assert build == {"trace_s": 6.0, "lower_s": 2.0, "backend_s": 0.5,
                     "cache_fetch_s": 3.0, "rest_s": 0.5, "s": 12.0,
                     "ops": 7, "blocks": 2, "first_steps_s": 38.0}
    assert line["trace"] == {
        "wall_s": 6.0, "operators_self_s": 4.0, "outside_operator_s": 2.0,
        "classes": [["Linear", 2.5], ["Conv2D", 1.5]],
        "blocks": [["1", 1.25], ["0", 0.75]]}
    # a kernel's traces beside the traced calls of its family
    assert line["kernels_traced_calls"] == [
        ["ff_ce_bwd", 1, 1], ["ff_flash_fwd", 10, 3],
        ["ff_flash_win_fwd", 4, 2]]
    window = line["window"]
    assert window["s"] == 4.0 and window["between_intervals_s"] == 3.0
    assert window["steps"] == 5              # the one in the pause too
    assert window["interval_step_ms"] == [1000.0, 1000.0]
    assert window["longest"] == [
        ["ff:runtime.step", {"n": 4}, 1000.0, 1],
        ["bench:fence", {}, 1000.0, 0], ["bench:fence", {}, 1000.0, 1],
        ["ff:runtime.gc", {"generation": 2, "collected": 5}, 200.0, 0],
        ["ff:runtime.step", {"n": 5}, 200.0, 1]]
    assert window["longest_by_name"]["ff:runtime.prefetch_wait"] == [
        {"batch": 3}, 10.0, 1]
    assert window["records_short"] == [] and "refused" not in line
    assert line["metrics"]["runtime.gc_pause_share"] == pytest.approx(7.5)


def _old_program():
    """A program from before PR 36: spans and counters, none of these."""
    snap = _snap()
    keep = ("ff:runtime.prefetch_wait",)
    snap["records"] = [r for r in snap["records"] if r["name"] in keep]
    snap["spans"] = {k: v for k, v in snap["spans"].items() if k in keep}
    for key in ("counters", "counter_history"):
        snap[key] = {k: v for k, v in snap[key].items()
                     if k.startswith("kernels.flash.")}
    del snap["dropped"]
    return snap


def _dropped():
    """The buffer let 9 step records go and its oldest starts inside the
    window; the collector's history starts after the window opened."""
    snap = _snap()
    snap["records"] = [r for r in snap["records"]
                       if r["name"] != "ff:runtime.step" or r["start"] > 100.2]
    snap["dropped"] = {"ff:runtime.step": 9}
    snap["counter_history"]["runtime.gc_s"] = \
        snap["counter_history"]["runtime.gc_s"][1:]
    return snap


@pytest.mark.parametrize("case, none", [
    ("no_facts", METRICS), ("old_program", METRICS),
    ("dropped", ("runtime.host_dispatch_share", "runtime.gc_pause_share")),
])
def test_reader_returns_none_and_says_so_never_a_share_of_a_part(
        monkeypatch, capsys, case, none):
    snap = {"old_program": _old_program(), "dropped": _dropped(),
            "no_facts": _snap()}[case]
    _with(monkeypatch, snap)
    facts = {} if case == "no_facts" else _facts()
    got = {name: _reader(name).read(facts) for name in METRICS}
    assert {k for k, v in got.items() if v is None} == set(none)
    said = capsys.readouterr().out.splitlines()
    if case == "no_facts":
        assert said == []
        return
    line = json.loads(said[0].split(" ", 2)[2])
    assert set(line["refused"]) == set(none)
    if case == "dropped":
        assert "9 ff:runtime.step records dropped" in \
            line["refused"]["runtime.host_dispatch_share"]
        assert line["window"]["records_short"] == ["ff:runtime.step"]
        assert got["entry.step_build_s"] == 12.0


def test_records_dropped_before_the_window_do_not_refuse_a_share(
        monkeypatch):
    snap = _snap()
    snap["dropped"] = {"ff:runtime.step": 40}    # all older than 99.0
    _with(monkeypatch, snap)
    host = ht.host_facts(_facts(), say=lambda s: None)
    assert host["metrics"]["runtime.host_dispatch_share"] == \
        pytest.approx(10.0)


@pytest.mark.parametrize("name", METRICS)
def test_entry_follows_the_schemas_rules_and_its_reader_declares_it(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["workloads"] == CELLS and entry["better"] == "lower"
    mod = _reader(name)
    assert mod.METRIC == {k: v for k, v in entry.items() if k != "workloads"}
    assert mod.__doc__
    older = [m for m in BENCH["per_layer"] if m["name"] not in METRICS]
    assert entry["layer"] in {m["layer"] for m in older}
    (moved,) = [m for m in BENCH["end_to_end"] if m["name"] == entry["moves"]]
    assert set(entry["workloads"]) <= set(moved.get("workloads", CELLS))
    # appended: the four stand last, in this order
    assert [m["name"] for m in BENCH["per_layer"]][-4:] == list(METRICS)


# --- one traced rehearsal, as the driver runs the benchmark ----------------

@pytest.fixture(scope="module")
def rehearsal():
    cmd = [sys.executable] + BENCH["command"][1:] + [
        "--workload", "alexnet_owt.train_1chip_b2048", "--seed",
        str(2**31 + 36), "--seconds", "1", "--trace", "1",
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


def test_rehearsal_prints_one_line_whose_parts_add_up(rehearsal):
    at, line = _said(rehearsal, "host_timeline")      # exactly one
    assert at < len(rehearsal) - 1 and "refused" not in line
    build = line["step_build"]
    parts = sum(build[k] for k in ("trace_s", "lower_s", "backend_s",
                                   "cache_fetch_s", "rest_s"))
    assert parts == pytest.approx(build["s"], rel=0.02)
    assert build["trace_s"] > 0 and build["backend_s"] > 0
    trace = line["trace"]
    assert trace["operators_self_s"] + trace["outside_operator_s"] == \
        pytest.approx(trace["wall_s"], rel=0.02)
    assert {c for c, _ in trace["classes"]} >= {"Conv2D", "Linear"}
    window = line["window"]
    assert window["steps"] >= 1 and window["longest"]
    assert len(window["interval_step_ms"]) >= 1


def test_rehearsal_reports_the_four_metrics_within_their_phases(rehearsal):
    _, line = _said(rehearsal, "host_timeline")
    _, phases = _said(rehearsal, "phases")
    metrics = json.loads(rehearsal[-1])["metrics"]
    for name in METRICS:
        assert metrics[name]["value"] == pytest.approx(
            line["metrics"][name], abs=1e-5)
    assert 0 < metrics["entry.trace_wall_s"]["value"] <= \
        phases["plan"] + phases["build_init"] + phases["warmup"]
    assert 0 < metrics["entry.step_build_s"]["value"] <= phases["warmup"]
    assert 0 < metrics["runtime.host_dispatch_share"]["value"] <= 100
    assert 0 <= metrics["runtime.gc_pause_share"]["value"] < 100
    # the sums at every nested level stay beside the wall seconds
    assert metrics["entry.trace_wall_s"]["value"] <= \
        metrics["entry.trace_lower_s"]["value"] + 1e-6


def test_rehearsal_charges_idle_gaps_to_the_steps_span(rehearsal):
    _, spans = _said(rehearsal, "program_spans")
    count, total, own = spans["spans"]["ff:runtime.step"]
    assert count >= 1 and total >= own > 0
    assert spans["spans"]["ff:entry.step_build"][0] == 1
    assert spans["counters"]["runtime.gc_s"] > 0
