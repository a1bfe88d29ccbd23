"""Step-budget / metrics-export / counter-lane tests (the MFU-waterfall
observability layer: obs/budget.py, obs/metrics.py, obs/trace.py counter
events, the report budget CLI, and fit()'s step_budget wiring).
Tier-1: CPU, 8-device virtual mesh, no slow marker."""

import json
import math
import os

import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.obs.budget import (build_step_budget, check_budget,
                                     mfu_waterfall, render_waterfall)
from flexflow_tpu.obs.metrics import MetricsExporter, read_textfile


# ---------------------------------------------------------------------------
# budget invariants


def test_budget_buckets_sum_to_wall():
    b = build_step_budget(1.0, compute_s=0.5, comm_s=0.2,
                          input_stall_s=0.1, host_sync_s=0.05,
                          checkpoint_s=0.05)
    assert not check_budget(b)
    bk = b["buckets"]
    assert all(v >= 0 for v in bk.values())
    assert abs(sum(bk.values()) - 1.0) < 1e-12
    assert abs(bk["residual"] - 0.1) < 1e-12
    assert not b["clamped"]


def test_budget_overcounting_instrument_is_clamped():
    # isolated op timings routinely exceed the fused step: the later
    # buckets must clamp to the remaining wall, never push the sum past
    # the clock
    b = build_step_budget(1.0, compute_s=1.7, comm_s=0.4,
                          input_stall_s=0.2)
    bk = b["buckets"]
    assert not check_budget(b)
    assert bk["compute"] == 1.0
    assert bk["comm"] == 0.0 and bk["input_stall"] == 0.0
    assert bk["residual"] == 0.0
    assert "compute" in b["clamped"] and "comm" in b["clamped"]
    # the pre-clamp estimates survive for honesty
    assert b["raw"]["compute"] == 1.7


def test_budget_negative_and_missing_inputs():
    b = build_step_budget(0.5, compute_s=-0.3, comm_s=None)
    bk = b["buckets"]
    assert bk["compute"] == 0.0  # negative clamps to zero, not clamped-flag
    assert bk["comm"] == 0.0
    assert b["sources"]["comm"] == "none"
    assert abs(bk["residual"] - 0.5) < 1e-12
    assert not check_budget(b)


def test_check_budget_flags_violations():
    assert check_budget({"step_wall_s": -1.0, "buckets": {}})
    assert check_budget({"step_wall_s": 1.0, "buckets": {"x": -0.5}})
    bad = {"step_wall_s": 1.0, "buckets": {"a": 0.8, "b": 0.9}}
    assert any("sum" in e for e in check_budget(bad))
    assert check_budget({"step_wall_s": 1.0, "buckets": None})


# ---------------------------------------------------------------------------
# the waterfall join


def _stream(flops=8e9, bytes_=1e9, wall=0.02):
    bud = build_step_budget(wall, compute_s=wall * 0.5, comm_s=wall * 0.3,
                            input_stall_s=wall * 0.1)
    return [
        {"kind": "run_start", "devices": 8, "platform": "tpu",
         "device_kind": "TPU v5 lite"},
        {"kind": "compile", "seconds": 1.0, "flops": flops,
         "bytes_accessed": bytes_},
        {"kind": "summary", "images_per_sec": 1000.0},
        dict(bud, kind="step_budget"),
    ]


def test_waterfall_joins_budget_and_roofline():
    wf = mfu_waterfall(_stream())
    assert wf is not None
    assert wf["devices"] == 8
    assert wf["mfu"] is not None and wf["mfu_ceiling"] is not None
    assert wf["mfu"] <= wf["mfu_ceiling"] + 1e-12
    # rows are descending by seconds and cover the removable buckets
    secs = [r["seconds"] for r in wf["rows"]]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) <= wf["step_wall_s"] + 1e-12
    # removing buckets only improves (or holds) MFU
    mfus = [r["mfu_after"] for r in wf["rows"] if r["mfu_after"]]
    assert all(b >= a - 1e-12 for a, b in zip(mfus, mfus[1:]))
    lines = render_waterfall(wf)
    text = "\n".join(lines)
    assert "MFU waterfall" in text and "remove bucket" in text
    assert "biggest lever" in text


def test_waterfall_without_cost_analysis_is_seconds_only():
    evs = [e for e in _stream() if e["kind"] != "compile"]
    wf = mfu_waterfall(evs)
    assert wf["mfu"] is None and wf["mfu_ceiling"] is None
    assert wf["rows"]  # seconds still rank
    text = "\n".join(render_waterfall(wf))
    assert "seconds-only" in text


def test_waterfall_peaks_are_the_stream_devices():
    """A utilization is a fraction of the chip the stream was recorded
    on: none for a CPU stream, an error for a TPU kind with no peaks."""
    def on(**device):
        return [dict(e, **device) if e["kind"] == "run_start" else e
                for e in _stream()]

    wf = mfu_waterfall(on(platform="cpu", device_kind="cpu"))
    assert wf["mfu"] is None and wf["mfu_ceiling"] is None and wf["rows"]
    assert wf["flops_per_step"] == 8e9       # the counts are still there
    with pytest.raises(ValueError, match="no peak numbers"):
        mfu_waterfall(on(device_kind="TPU v9"))


def test_waterfall_requires_budget_record():
    assert mfu_waterfall([{"kind": "compile", "flops": 1.0}]) is None


# ---------------------------------------------------------------------------
# metrics exporter


def test_metrics_textfile_roundtrip(tmp_path):
    path = str(tmp_path / "m.prom")
    ex = MetricsExporter(path, meta={"model": "Toy", "run": "r1"})
    ex.update(mfu=0.31, throughput_items_per_sec=1900.5, steps_total=7,
              loss=float("nan"), hbm_live_bytes=None,
              bad_inf=float("inf"))
    ex.write()
    vals = read_textfile(path)
    assert vals["mfu"] == pytest.approx(0.31)
    assert vals["throughput_items_per_sec"] == pytest.approx(1900.5)
    assert vals["steps_total"] == 7
    # non-finite / None gauges are DROPPED, never published
    assert "loss" not in vals and "hbm_live_bytes" not in vals
    assert "bad_inf" not in vals
    assert all(math.isfinite(v) for v in vals.values())
    # prometheus exposition structure: TYPE lines for every sample
    text = open(path).read()
    assert "# TYPE ff_mfu gauge" in text
    assert "# TYPE ff_steps_total counter" in text
    assert 'ff_run_info{model="Toy",run="r1"} 1' in text
    # the JSON snapshot mirrors the gauges
    snap = json.load(open(path + ".json"))
    assert snap["gauges"]["mfu"] == pytest.approx(0.31)
    assert snap["meta"]["model"] == "Toy"


def test_metrics_rewrite_is_atomic_update(tmp_path):
    path = str(tmp_path / "m.prom")
    ex = MetricsExporter(path)
    ex.update(mfu=0.1)
    ex.write()
    ex.update(mfu=0.2, loss=1.5)
    ex.write()
    vals = read_textfile(path)
    assert vals["mfu"] == pytest.approx(0.2)
    assert vals["loss"] == pytest.approx(1.5)
    # no tempfile litter from the atomic replace
    assert [f for f in os.listdir(tmp_path)
            if f.startswith(".metrics-")] == []


def test_metrics_parser_rejects_malformed(tmp_path):
    p = tmp_path / "bad.prom"
    p.write_text("ff_mfu 0.3 extra-token\n")
    with pytest.raises(ValueError):
        read_textfile(str(p))


# ---------------------------------------------------------------------------
# counter lanes


def _counter_records():
    return [
        {"kind": "step", "step": 1, "wall_ms": 10.0,
         "images_per_sec": 800.0},
        {"kind": "step", "step": 2, "wall_ms": 10.0,
         "images_per_sec": 820.0},
        {"kind": "metrics", "steps_total": 2, "mfu": 0.33,
         "hbm_live_bytes": 1e9, "hbm_peak_bytes": 2e9},
    ]


def test_counter_lanes_validate():
    from flexflow_tpu.obs.trace import (chrome_trace, fit_counter_events,
                                        fit_trace_events, validate_trace)

    counters = fit_counter_events(_counter_records())
    names = {e["name"] for e in counters}
    assert names == {"imgs/s", "MFU", "HBM bytes"}
    assert all(e["ph"] == "C" for e in counters)
    # metrics sample lands at the cumulative wall time of its step count
    (mfu_ev,) = [e for e in counters if e["name"] == "MFU"]
    assert mfu_ev["ts"] == pytest.approx(20e3)  # 2 steps x 10 ms, in us
    # merged into the fit lanes and past the validator
    trace = chrome_trace(fit_trace_events(_counter_records()))
    assert validate_trace(trace) == []
    assert [e for e in trace["traceEvents"] if e.get("ph") == "C"]


def test_validate_trace_rejects_bad_counters():
    from flexflow_tpu.obs.trace import validate_trace

    base = {"name": "c", "ph": "C", "pid": 2, "ts": 0.0}
    assert validate_trace(
        {"traceEvents": [dict(base, args={})]})  # empty series
    assert validate_trace(
        {"traceEvents": [dict(base, args={"v": float("nan")})]})
    assert validate_trace(
        {"traceEvents": [dict(base, args={"v": "high"})]})
    assert validate_trace(
        {"traceEvents": [dict(base, ts=-1.0, args={"v": 1.0})]})
    assert validate_trace(
        {"traceEvents": [dict(base, args={"v": 1.0})]}) == []


# ---------------------------------------------------------------------------
# fit wiring end-to-end (8-dev mesh): step_budget + metrics + report


def _small_model(machine, cfg):
    from flexflow_tpu.model import FFModel

    ff = FFModel(cfg, machine)
    img = ff.create_input((8, 16, 16, 3), name="image")
    t = ff.conv2d("conv1", img, 8, 3, 3, 1, 1, 1, 1, relu=True)
    t = ff.flat("flat", t)
    t = ff.linear("fc", t, 8, relu=False)
    ff.softmax("softmax", t)
    return ff


@pytest.fixture(scope="module")
def budget_run(tmp_path_factory, machine8):
    """One shared fit run with sampling + metrics on, reused by the
    assertions below (fit+compile is the expensive part)."""
    from flexflow_tpu.data import synthetic_batches
    from flexflow_tpu.obs import read_run

    tmp = tmp_path_factory.mktemp("budget")
    cfg = FFConfig(batch_size=8, input_height=16, input_width=16,
                   num_iterations=4, print_freq=2, num_classes=8,
                   obs_dir=str(tmp / "obs"), run_id="budget-e2e",
                   op_time_every=2,
                   metrics_path=str(tmp / "metrics.prom"))
    ff = _small_model(machine8, cfg)
    data = synthetic_batches(machine8, 8, 16, 16, num_classes=8,
                             mode="ones")
    out = ff.fit(data, num_iterations=4, log=lambda *a: None)
    return cfg, out, list(read_run(out["obs_path"]))


def test_fit_emits_sound_step_budget(budget_run):
    cfg, out, evs = budget_run
    (bud,) = [e for e in evs if e["kind"] == "step_budget"]
    assert not check_budget(bud)
    assert bud["n_samples"] == 2
    assert bud["sources"]["wall"] == "sampled_step"
    # buckets sum to <= the measured step wall (the acceptance invariant)
    assert sum(bud["buckets"].values()) <= bud["step_wall_s"] * (1 + 1e-6)
    assert set(bud["buckets"]) == {"compute", "comm", "input_stall",
                                   "host_sync", "checkpoint", "residual"}


def test_fit_metrics_export_finite(budget_run):
    cfg, out, evs = budget_run
    assert out["metrics_path"] == cfg.metrics_path
    vals = read_textfile(cfg.metrics_path)
    for key in ("throughput_items_per_sec", "images_per_sec",
                "steps_total", "step_wall_seconds"):
        assert key in vals and math.isfinite(vals[key]), (key, vals)
    # a CPU has no peak to be a fraction of: no utilization is published
    assert "mfu" not in vals and "mfu_ceiling" not in vals
    assert vals["steps_total"] == 4
    # every published snapshot is mirrored into the obs stream
    mets = [e for e in evs if e["kind"] == "metrics"]
    assert mets and mets[-1]["steps_total"] == 4
    assert mets[-1]["path"] == cfg.metrics_path


def test_fit_counter_lanes_from_real_stream(budget_run):
    from flexflow_tpu.obs.trace import (chrome_trace, fit_trace_events,
                                        validate_trace)

    _, _, evs = budget_run
    trace = chrome_trace(fit_trace_events(evs))
    assert validate_trace(trace) == []
    names = {e["name"] for e in trace["traceEvents"]
             if e.get("ph") == "C"}
    # the MFU lane needs a TPU stream (test_counter_lanes_validate has one)
    assert "imgs/s" in names and "MFU" not in names


def test_report_budget_cli_on_obs_dir(budget_run, capsys):
    from flexflow_tpu.apps import report

    cfg, _, _ = budget_run
    rc = report.main(["budget", cfg.obs_dir])
    text = capsys.readouterr().out
    assert rc == 0
    assert "MFU waterfall" in text and "remove bucket" in text
    rc = report.main(["budget", cfg.obs_dir, "--json"])
    js = json.loads(capsys.readouterr().out)
    assert rc == 0 and js["violations"] == []
    assert js["waterfall"]["rows"]


def test_report_budget_without_record_explains(tmp_path, capsys):
    from flexflow_tpu.apps import report

    p = tmp_path / "r.jsonl"
    p.write_text(json.dumps({"kind": "run_start", "run": "x"}) + "\n")
    rc = report.main(["budget", str(p)])
    assert rc == 1
    assert "no step_budget record" in capsys.readouterr().out


def test_summarize_roundtrips_budget_and_metrics(budget_run):
    from flexflow_tpu.obs.report import render, summarize

    _, _, evs = budget_run
    s = summarize(evs)
    assert "step_budget" in s and "metrics" in s
    assert not check_budget({"step_wall_s": s["step_budget"]["step_wall_s"],
                             "buckets": s["step_budget"]["buckets"]})
    assert math.isfinite(s["metrics"]["gauges"]["step_wall_seconds"])
    # and the prose renderer names both
    text = render(evs)
    assert "step budget" in text and "metrics export" in text


def test_metrics_and_budget_flags_parse():
    cfg = FFConfig.from_args(["--metrics-path", "/tmp/m.prom",
                              "--op-time-every", "4"])
    assert cfg.metrics_path == "/tmp/m.prom" and cfg.op_time_every == 4
    from flexflow_tpu.apps.lm import parse_args as lm_parse
    from flexflow_tpu.apps.nmt import parse_args as nmt_parse

    lm = lm_parse(["--metrics-path", "x.prom", "--op-time-every", "3"])
    assert lm.metrics_path == "x.prom" and lm.op_time_every == 3
    nm = nmt_parse(["--metrics-path", "y.prom", "--op-time-every", "2"])
    assert nm.metrics_path == "y.prom" and nm.op_time_every == 2


def test_calibrate_from_obs_excludes_budget_buckets(tmp_path, capsys):
    """The compute-only discipline: input-stall / host-sync / checkpoint
    buckets from step_budget are subtracted before the residual is
    blamed on collectives — the comm scale shrinks accordingly."""
    from flexflow_tpu.apps.calibrate import calibrate_from_obs

    def _write(path, events):
        with open(path, "w") as f:
            for e in events:
                f.write(json.dumps(e) + "\n")

    base = [
        {"kind": "sim_drift", "measured_s": 0.10, "value": 2.0},
        {"kind": "search_breakdown", "opt_stream_s": 0.0,
         "ops": [{"op": "a", "kind": "Conv2D", "compute_s": 0.01,
                  "collective_s": 0.01}]},
    ]
    d1 = tmp_path / "legacy"
    d1.mkdir()
    _write(d1 / "r.jsonl", base)
    legacy = calibrate_from_obs(str(d1), log=lambda *a: None)
    # residual 0.09 / sim_comm 0.01 -> 9.0
    assert legacy["collective_scale"] == pytest.approx(9.0)
    assert legacy["budget_excluded_s"] == 0.0

    d2 = tmp_path / "budgeted"
    d2.mkdir()
    bud = build_step_budget(0.10, compute_s=0.01, comm_s=0.02,
                            input_stall_s=0.03, host_sync_s=0.01,
                            checkpoint_s=0.01)
    _write(d2 / "r.jsonl", base + [dict(bud, kind="step_budget")])
    fitted = calibrate_from_obs(str(d2), log=lambda *a: None)
    # 0.05 s of stall/sync/ckpt excluded: residual 0.04 -> scale 4.0
    assert fitted["budget_excluded_s"] == pytest.approx(0.05)
    assert fitted["collective_scale"] == pytest.approx(4.0)
    assert fitted["collective_scale"] < legacy["collective_scale"]
