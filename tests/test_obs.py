"""Run-telemetry subsystem tests (obs package): record schema round-trip,
the three wired surfaces (fit / search / bench), and the report CLI.
Tier-1: CPU, 8-device virtual mesh, no slow marker."""

import json
import os
import threading

import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.data import synthetic_batches
from flexflow_tpu.model import FFModel
from flexflow_tpu.obs import NULL, RunLog, new_run_id, read_events
from flexflow_tpu.strategy import ParallelConfig, Strategy


def _small_model(machine, cfg):
    ff = FFModel(cfg, machine)
    img = ff.create_input((8, 16, 16, 3), name="image")
    t = ff.conv2d("conv1", img, 8, 3, 3, 1, 1, 1, 1, relu=True)
    t = ff.flat("flat", t)
    t = ff.linear("fc", t, 8, relu=False)
    ff.softmax("softmax", t)
    return ff


def _cfg(tmp_path, **kw):
    kw.setdefault("obs_dir", str(tmp_path))
    return FFConfig(batch_size=8, input_height=16, input_width=16,
                    num_iterations=3, print_freq=0, num_classes=8, **kw)


# ---------------------------------------------------------------------------
# record schema


def test_runlog_roundtrip(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with RunLog(path, run_id="r1", surface="test",
                meta={"who": "tester"}) as ol:
        assert ol.enabled
        ol.event("custom", a=1, b="two", nested={"c": [1, 2]})
        ol.counter("widgets", 3)
        ol.gauge("pressure", 0.5, unit="bar")
        with ol.timer("slept"):
            pass
    evs = list(read_events(path))
    kinds = [e["kind"] for e in evs]
    assert kinds == ["run_start", "custom", "counter", "gauge", "timer"]
    # every record carries run id, timestamp, surface
    for e in evs:
        assert e["run"] == "r1"
        assert isinstance(e["ts"], float)
        assert e["surface"] == "test"
    assert evs[0]["who"] == "tester"
    assert evs[1]["a"] == 1 and evs[1]["nested"] == {"c": [1, 2]}
    assert evs[2] == {**evs[2], "name": "widgets", "value": 3}
    assert evs[3]["unit"] == "bar"
    assert evs[4]["seconds"] >= 0.0
    # timestamps are non-decreasing (file order == emit order)
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)


def test_runlog_thread_safety(tmp_path):
    path = str(tmp_path / "threads.jsonl")
    ol = RunLog(path, run_id="rt")

    def emit(i):
        for j in range(50):
            ol.event("tick", worker=i, j=j)

    threads = [threading.Thread(target=emit, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    ol.close()
    # no torn lines: every line parses, all 201 records present
    with open(path) as f:
        lines = [l for l in f if l.strip()]
    assert len(lines) == 1 + 4 * 50
    for l in lines:
        json.loads(l)


def test_null_log_is_inert_and_cheap(tmp_path):
    assert not NULL.enabled and not NULL
    NULL.event("anything", x=1)
    NULL.counter("c")
    NULL.gauge("g", 1.0)
    with NULL.timer("t"):
        pass
    NULL.close()
    # from_config gates on obs_dir
    from flexflow_tpu import obs

    assert obs.from_config(FFConfig()) is NULL
    live = obs.from_config(_cfg(tmp_path, run_id="gate"), surface="fit")
    assert live.enabled and live.run_id == "gate"
    live.close()


def test_runlog_rotation(tmp_path):
    from flexflow_tpu.obs import read_run, run_files

    path = str(tmp_path / "rot.jsonl")
    ol = RunLog(path, run_id="rr", max_bytes=400)
    for i in range(50):
        ol.event("tick", i=i, pad="x" * 40)
    ol.close()
    files = run_files(path)
    assert len(files) > 1, "400-byte cap must have rolled the stream"
    assert files[0] == path and files[1] == path + ".1"
    # nothing lost, order preserved across parts
    ticks = [e["i"] for e in read_run(path) if e["kind"] == "tick"]
    assert ticks == list(range(50))
    # reopening resumes in the NEWEST part (no shuffle of old parts)
    before = files[:-1]
    sizes = [os.path.getsize(f) for f in before]
    ol2 = RunLog(path, run_id="rr", max_bytes=400)
    ol2.event("more")
    ol2.close()
    assert [os.path.getsize(f) for f in before] == sizes
    assert [e["kind"] for e in read_run(path)][-1] == "more"
    # max_bytes=0 disables rotation
    p2 = str(tmp_path / "norot.jsonl")
    ol3 = RunLog(p2, run_id="nr", max_bytes=0)
    for i in range(50):
        ol3.event("tick", i=i, pad="x" * 40)
    ol3.close()
    assert run_files(p2) == [p2]


def test_read_events_skips_torn_tail(tmp_path):
    path = str(tmp_path / "torn.jsonl")
    with RunLog(path, run_id="r") as ol:
        ol.event("ok")
    with open(path, "a") as f:
        f.write('{"kind": "torn", "run"')  # crashed writer's tail
    kinds = [e["kind"] for e in read_events(path)]
    assert kinds == ["run_start", "ok"]


def test_new_run_id_unique():
    assert new_run_id() != new_run_id()


# ---------------------------------------------------------------------------
# fit surface


def test_fit_emits_records(tmp_path, machine8):
    cfg = _cfg(tmp_path, run_id="fitrun")
    ff = _small_model(machine8, cfg)
    data = synthetic_batches(machine8, 8, 16, 16, num_classes=8,
                             mode="ones")
    out = ff.fit(data, num_iterations=3, log=lambda *a: None)
    # satellite: losses are plain floats (one bulk conversion post-loop)
    assert all(isinstance(l, float) for l in out["loss"])
    assert out["run_id"] == "fitrun"
    evs = list(read_events(out["obs_path"]))
    by_kind = {}
    for e in evs:
        by_kind.setdefault(e["kind"], []).append(e)
    assert "run_start" in by_kind and "compile" in by_kind
    assert len(by_kind["step"]) == 3
    for i, s in enumerate(by_kind["step"]):
        assert s["step"] == i + 1
        assert s["wall_ms"] > 0
        assert s["images_per_sec"] > 0
    # step losses mirror the returned loss list
    assert [s["loss"] for s in by_kind["step"]] == out["loss"]
    (summary,) = by_kind["summary"]
    assert summary["iterations"] == 3
    assert summary["final_loss"] == out["loss"][-1]
    # compile record: first-call seconds + post-fusion cost analysis
    comp = by_kind["compile"][0]
    assert comp["seconds"] > 0
    assert comp.get("flops", 0) > 0


def test_fit_obs_disabled_is_unchanged(tmp_path, machine8):
    cfg = FFConfig(batch_size=8, input_height=16, input_width=16,
                   num_iterations=2, print_freq=0, num_classes=8)
    ff = _small_model(machine8, cfg)
    data = synthetic_batches(machine8, 8, 16, 16, num_classes=8,
                             mode="ones")
    out = ff.fit(data, num_iterations=2, log=lambda *a: None)
    assert out["run_id"] is None and out["obs_path"] is None
    assert all(isinstance(l, float) for l in out["loss"])
    assert list(tmp_path.iterdir()) == []  # nothing written anywhere


def test_fit_sim_drift_from_artifact(tmp_path, machine8):
    s = Strategy()
    s["fc"] = ParallelConfig((1, 8), tuple(range(8)))
    s.predicted = {"best_time_s": 0.001}
    spath = str(tmp_path / "strat.json")
    s.save(spath)
    cfg = _cfg(tmp_path, run_id="drift", strategy_file=spath)
    assert cfg.strategies.predicted == {"best_time_s": 0.001}
    ff = _small_model(machine8, cfg)
    data = synthetic_batches(machine8, 8, 16, 16, num_classes=8,
                             mode="ones")
    out = ff.fit(data, num_iterations=3, log=lambda *a: None)
    (drift,) = [e for e in read_events(out["obs_path"])
                if e["kind"] == "sim_drift"]
    assert drift["source"] == "artifact"
    assert drift["predicted_s"] == 0.001
    assert drift["measured_s"] > 0
    assert abs(drift["value"] - drift["measured_s"] / 0.001) < 1e-9


def test_fit_sim_drift_analytic_fallback(tmp_path, machine8):
    # a searched strategy WITHOUT a carried prediction: fit prices it
    # through the simulator (assignment_for + native sim)
    s = Strategy()
    s["fc"] = ParallelConfig((1, 8), tuple(range(8)))
    cfg = _cfg(tmp_path, run_id="drift2")
    cfg.strategies = s
    ff = _small_model(machine8, cfg)
    data = synthetic_batches(machine8, 8, 16, 16, num_classes=8,
                             mode="ones")
    out = ff.fit(data, num_iterations=3, log=lambda *a: None)
    (drift,) = [e for e in read_events(out["obs_path"])
                if e["kind"] == "sim_drift"]
    assert drift["source"] == "analytic"
    assert drift["predicted_s"] > 0 and drift["value"] > 0


def test_fit_resume_emits_ckpt_fallback(tmp_path, machine8):
    """Crash consistency end-to-end (robustness round): the latest
    checkpoint is truncated on disk; a fresh fit() must cascade to the
    prior step, emit a ckpt_fallback record, and resume training."""
    import os

    ckdir = str(tmp_path / "ckpt")
    cfg = _cfg(tmp_path, run_id="fb1", ckpt_dir=ckdir, ckpt_freq=2)
    ff = _small_model(machine8, cfg)
    data = synthetic_batches(machine8, 8, 16, 16, num_classes=8,
                             mode="ones")
    ff.fit(data, num_iterations=4, log=lambda *a: None)
    from flexflow_tpu.utils import checkpoint as ckpt

    assert ckpt.latest_step(ckdir) == 4
    ap = os.path.join(ckdir, "step_00000004", "arrays.npz")
    with open(ap, "r+b") as f:  # torn write on the latest step
        f.truncate(os.path.getsize(ap) // 2)

    cfg2 = _cfg(tmp_path, run_id="fb2", ckpt_dir=ckdir, ckpt_freq=2)
    ff2 = _small_model(machine8, cfg2)
    data2 = synthetic_batches(machine8, 8, 16, 16, num_classes=8,
                              mode="ones")
    with pytest.warns(RuntimeWarning, match="checkpoint fallback"):
        out = ff2.fit(data2, num_iterations=6, log=lambda *a: None)
    evs = list(read_events(out["obs_path"]))
    (fb,) = [e for e in evs if e["kind"] == "ckpt_fallback"]
    assert fb["from_step"] == 4 and fb["to_step"] == 2
    (res,) = [e for e in evs if e["kind"] == "checkpoint_restore"]
    assert res["step"] == 2
    # the run resumed from step 2 and completed the remaining 4 iters
    assert len(out["loss"]) == 4
    assert ckpt.latest_step(ckdir) == 6


# ---------------------------------------------------------------------------
# search surface


def _searcher(machine8, tmp_path, run_id="search"):
    from flexflow_tpu.sim.search import StrategySearch

    cfg = FFConfig(batch_size=16, input_height=16, input_width=16,
                   num_classes=8)
    ff = _small_model(machine8, cfg)
    ol = RunLog(str(tmp_path / f"{run_id}.jsonl"), run_id=run_id,
                surface="search")
    return StrategySearch(ff, machine8, obs=ol), ol


@pytest.mark.native
def test_search_trace_monotone_best_cost(tmp_path, machine8):
    ss, ol = _searcher(machine8, tmp_path)
    strategy, info = ss.search(iters=2000, seed=1)
    ol.close()
    evs = list(read_events(ol.path))
    by_kind = {}
    for e in evs:
        by_kind.setdefault(e["kind"], []).append(e)
    (space,) = by_kind["search_space"]
    assert space["ops"] == len(ss.ops)
    assert space["candidates"] > 0
    chunks = by_kind["search_chunk"]
    assert chunks and len(chunks) == len(info["trace"])
    curve = [c["best_time_s"] for c in chunks]
    assert all(a >= b - 1e-12 for a, b in zip(curve, curve[1:])), \
        "best-cost curve must be non-increasing"
    assert curve[-1] == info["best_time"]
    # acceptance-rate stats present and sane
    acc = sum(c["accepted"] for c in chunks)
    prop = sum(c["proposed"] for c in chunks)
    assert 0 <= acc <= prop
    assert abs(info["accept_rate"] - (acc / prop if prop else 0.0)) < 1e-12
    (result,) = by_kind["search_result"]
    assert result["dp_time_s"] == info["dp_time"]
    assert result["best_time_s"] == info["best_time"]
    # winning-strategy per-op breakdown covers every real op
    (bd,) = by_kind["search_breakdown"]
    named = {r["op"] for r in bd["ops"]}
    assert named == {"conv1", "flat", "fc", "softmax"}
    assert all(r["compute_s"] > 0 for r in bd["ops"])


@pytest.mark.native
def test_search_chunked_matches_info_and_strategy(tmp_path, machine8):
    # the chunked chain still returns an executable strategy whose
    # simulated cost equals info["best_time"]
    ss, ol = _searcher(machine8, tmp_path, run_id="s2")
    strategy, info = ss.search(iters=1000, seed=7)
    ol.close()
    assign = ss.assignment_for(strategy)
    assert ss.simulate(assign) == info["best_time"]
    assert info["speedup_vs_dp"] >= 1.0 - 1e-9


@pytest.mark.native
def test_assignment_for_rejects_foreign_pc(machine8, tmp_path):
    ss, ol = _searcher(machine8, tmp_path, run_id="s3")
    ol.close()
    foreign = Strategy()
    foreign["conv1"] = ParallelConfig((1, 1, 1, 3), (0, 1, 2))
    with pytest.raises(KeyError):
        ss.assignment_for(foreign)


@pytest.mark.native
def test_search_multichain_per_chain_monotone(tmp_path, machine8):
    """chains=2: one search_chunk record per chain per chunk, each chain's
    best-cost trajectory non-increasing, delta-hit rate reported, and the
    final best equals the best chain's last best."""
    ss, ol = _searcher(machine8, tmp_path, run_id="mc")
    strategy, info = ss.search(iters=1200, seed=3, chains=2, chunks=4)
    ol.close()
    evs = list(read_events(ol.path))
    chunks = [e for e in evs if e["kind"] == "search_chunk"]
    by_chain = {}
    for c in chunks:
        by_chain.setdefault(c["chain"], []).append(c)
    assert set(by_chain) == {0, 1}
    for cid, recs in by_chain.items():
        curve = [r["best_time_s"] for r in recs]
        assert all(a >= b - 1e-12 for a, b in zip(curve, curve[1:])), \
            f"chain {cid} best-cost curve must be non-increasing: {curve}"
        for r in recs:
            assert 0.0 <= r["delta_hit_rate"] <= 1.0
            assert r["proposals_per_sec"] >= 0.0
    assert info["chains"] == 2
    assert info["best_time"] == min(
        recs[-1]["best_time_s"] for recs in by_chain.values())
    (result,) = [e for e in evs if e["kind"] == "search_result"]
    assert result["chains"] == 2
    assert result["cost_cache"] == {"hits": 0, "misses": 0}  # analytic
    # deterministic across runs: same seed, same chains -> same plan
    ss2, ol2 = _searcher(machine8, tmp_path, run_id="mc2")
    _, info2 = ss2.search(iters=1200, seed=3, chains=2, chunks=4)
    ol2.close()
    assert info2["assignment"] == info["assignment"]
    assert info2["best_time"] == info["best_time"]


# ---------------------------------------------------------------------------
# bench surface (stdout hygiene) — bench.run monkeypatched, no training


def test_bench_single_json_stdout_line(tmp_path, monkeypatch, capsys):
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        import bench
    finally:
        sys.path.pop(0)

    def fake_run(model="inception", strategy_file=None, **kw):
        print("library noise on stdout")  # must NOT reach real stdout
        return (100.0, 800.0, 1.0, 0.5,
                {"windows": 1, "min": 99.0, "max": 101.0},
                {"input_stall_s": 0.002, "regrid_hops": 3})

    monkeypatch.setattr(bench, "run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.setenv("BENCH_OBS_DIR", str(tmp_path / "obs"))
    bench.main()
    captured = capsys.readouterr()
    lines = [l for l in captured.out.splitlines() if l.strip()]
    assert len(lines) == 1, f"stdout must be ONE JSON line, got {lines}"
    rec = json.loads(lines[0])
    assert rec["value"] == 100.0
    # the round-6 execution-performance fields ride the metric line
    assert rec["input_stall_s"] == 0.002 and rec["regrid_hops"] == 3
    assert "noise" in captured.err
    # run identity rides in the metric record, and the obs file has it
    assert rec["run_id"] and rec["obs_path"]
    evs = list(read_events(rec["obs_path"]))
    (b,) = [e for e in evs if e["kind"] == "bench"]
    assert b["value"] == 100.0 and b["run"] == rec["run_id"]


def test_bench_records_trace_path(tmp_path, monkeypatch, capsys):
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        import bench
    finally:
        sys.path.pop(0)

    def fake_run(model="inception", strategy_file=None, **kw):
        return (100.0, 800.0, 1.0, None,
                {"windows": 1, "min": 99.0, "max": 101.0},
                {"input_stall_s": 0.0, "regrid_hops": 0})

    strat = tmp_path / "s.json"
    strat.write_text("{}")
    # a sim trace the search exported next to the strategy rides the line
    (tmp_path / "s.trace.json").write_text('{"traceEvents": []}')
    monkeypatch.setattr(bench, "run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py", str(strat)])
    monkeypatch.setenv("BENCH_OBS_DIR", str(tmp_path / "obs"))
    bench.main()
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["trace_path"] == str(tmp_path / "s.trace.json")


# ---------------------------------------------------------------------------
# flags + report CLI


def test_obs_flags_parsed():
    cfg = FFConfig.from_args(["-obs-dir", "/tmp/o", "-run-id", "rid"])
    assert cfg.obs_dir == "/tmp/o" and cfg.run_id == "rid"
    cfg = FFConfig.from_args(["--obs-dir", "/tmp/o2", "--run-id", "r2"])
    assert cfg.obs_dir == "/tmp/o2" and cfg.run_id == "r2"
    from flexflow_tpu.apps.nmt import parse_args as nmt_args

    ncfg = nmt_args(["-obs-dir", "/tmp/n", "-run-id", "nr"])
    assert ncfg.obs_dir == "/tmp/n" and ncfg.run_id == "nr"
    from flexflow_tpu.apps.search import parse_args as s_args

    sopts = s_args(["alexnet", "-obs-dir", "/tmp/s", "-run-id", "sr"])
    assert sopts["obs_dir"] == "/tmp/s" and sopts["run_id"] == "sr"
    # -chains / -delta ride both parsers (PR 2)
    sopts = s_args(["alexnet", "-chains", "4", "-delta", "check"])
    assert sopts["chains"] == 4 and sopts["delta"] == "check"
    sopts = s_args(["alexnet", "-trace"])
    assert sopts["trace"] is True
    assert s_args(["alexnet"])["trace"] is False
    cfg = FFConfig.from_args(["-chains", "8", "-delta", "off"])
    assert cfg.search_chains == 8 and cfg.search_delta == "off"
    with pytest.raises(SystemExit):
        s_args(["alexnet", "-delta", "sometimes"])


def test_strategy_predicted_roundtrip(tmp_path):
    s = Strategy()
    s["fc"] = ParallelConfig((1, 4), (0, 1, 2, 3))
    s.predicted = {"best_time_s": 0.5, "dp_time_s": 1.0, "devices": 4}
    path = str(tmp_path / "p.json")
    s.save(path)
    s2 = Strategy.load(path)
    assert s2.predicted == s.predicted
    assert s2["fc"] == s["fc"]
    # proto wire format stays reference-compatible (predicted is JSON-only)
    s3 = Strategy.from_proto_bytes(s.to_proto_bytes())
    assert s3.predicted is None


@pytest.mark.native
def test_report_cli_renders_fit_and_search(tmp_path, machine8, capsys):
    cfg = _cfg(tmp_path, run_id="rep")
    ff = _small_model(machine8, cfg)
    data = synthetic_batches(machine8, 8, 16, 16, num_classes=8,
                             mode="ones")
    out = ff.fit(data, num_iterations=3, log=lambda *a: None)
    ss, ol = _searcher(machine8, tmp_path, run_id="rep-search")
    ss.search(iters=500, seed=2)
    ol.close()
    from flexflow_tpu.apps import report

    rc = report.main([out["obs_path"], ol.path])
    assert rc == 0
    rendered = capsys.readouterr().out
    assert "== training ==" in rendered
    assert "== strategy search ==" in rendered
    assert "best-cost curve" in rendered
    assert "acceptance:" in rendered
    # empty/garbage input does not crash the reader
    junk = tmp_path / "junk.jsonl"
    junk.write_text("not json\n")
    assert report.main([str(junk)]) == 0
