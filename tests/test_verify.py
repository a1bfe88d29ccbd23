"""Static strategy verifier tests (round 11): the three lint passes
(sync-freedom, donation/retrace, predicted-time grounded accept), the
exemption-file policy, the pipeline/NMT audit extensions, the lint obs
record + report rendering, and the repo checker tools.

Obs kinds exercised here (tools/check_obs_kinds.py requires every
emitted kind in >=1 test): lint, checkpoint_save, pipeline_candidate,
pipeline_decision, elastic_refused, elastic_rejoin.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu.machine import Topology
from flexflow_tpu.utils.hlo_audit import (audit_consistent_time,
                                          audit_in_process)
from flexflow_tpu.verify import donation_lint, sync_lint
from flexflow_tpu.verify.findings import (Finding, apply_exemptions,
                                          counts, load_exemptions)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# pass 1: sync-freedom — source AST leg


def _src_findings(body):
    src = textwrap.dedent(body)
    return sync_lint.source_sync_findings(src, "m.py", funcs=("fit",))


def test_injected_device_get_fails_pointedly():
    """The acceptance check: a synthetic per-step device_get in the fit
    hot path must fail the sync pass with a finding naming the call."""
    fs = _src_findings("""
        def fit(self):
            for it in range(n):
                loss = self._step()
                host = jax.device_get(loss)
            return host
    """)
    errs = [f for f in fs if f.severity == "error"]
    assert len(errs) == 1
    f = errs[0]
    assert f.pass_name == "sync" and f.code == "device_get"
    assert "m.py:fit:device_get" == f.where
    assert "m.py:5" in f.message and "sync-ok" in f.message


def test_float_of_device_value_flagged_but_config_float_is_not():
    fs = _src_findings("""
        def fit(self):
            lr = float(self.cfg.learning_rate)   # host-side: fine
            for it in range(n):
                loss = self._step()
                acc = float(loss)                # device sync: flagged
    """)
    errs = [f for f in fs if f.severity == "error"]
    assert [f.code for f in errs] == ["float"]
    assert "m.py:6" in errs[0].message


def test_sync_ok_marker_with_reason_approves():
    fs = _src_findings("""
        def fit(self):
            loss = self._step()
            # sync-ok: epoch-boundary logging, outside the timed window
            print(float(loss))
    """)
    assert [f for f in fs if f.severity == "error"] == []
    (ok,) = [f for f in fs if f.exempted]
    assert ok.code == "float" and "epoch-boundary" in ok.reason


def test_sync_ok_marker_without_reason_is_itself_an_error():
    fs = _src_findings("""
        def fit(self):
            loss = self._step()
            v = float(loss)  # sync-ok:
    """)
    (f,) = [f for f in fs if f.severity == "error"]
    assert "no reason" in f.message


def test_marker_found_across_multiline_comment_block():
    fs = _src_findings("""
        def fit(self):
            loss = self._step()
            # the losses of the drained window must land before the
            # regrid frees the buffers they live in
            # sync-ok: drain boundary, not per-step
            kept = [float(v) for v in jax.device_get([loss])]
    """)
    assert [f for f in fs if f.severity == "error"] == []
    assert all(f.exempted for f in fs)


def test_repo_model_fit_hot_path_is_clean():
    """model.py's fit/_fit syncs are all marked with reasons — the repo
    lints clean (what `make lint` asserts)."""
    with open(os.path.join(ROOT, "flexflow_tpu", "model.py")) as f:
        fs = sync_lint.source_sync_findings(f.read(),
                                            "flexflow_tpu/model.py")
    assert fs, "fit hot path has known approved syncs"
    assert [f for f in fs if not f.exempted] == []


# ---------------------------------------------------------------------------
# pass 1: jaxpr + HLO legs


def test_jaxpr_pass_catches_staged_host_callback():
    def step(x):
        jax.debug.print("x = {x}", x=x)
        return x * 2.0

    traced = jax.jit(step).trace(jnp.ones(4))
    fs = sync_lint.jaxpr_sync_findings(traced.jaxpr)
    # jax.debug.print stages its own primitive (debug_print);
    # jax.debug.callback stages debug_callback
    assert any(f.code == "jaxpr_host_prim"
               and "debug_print" in f.where for f in fs)
    cb = jax.jit(lambda x: (jax.debug.callback(lambda v: None, x),
                            x * 2.0)[1]).trace(jnp.ones(4))
    assert any("debug_callback" in f.where
               for f in sync_lint.jaxpr_sync_findings(cb.jaxpr))

    clean = jax.jit(lambda x: x * 2.0).trace(jnp.ones(4))
    assert sync_lint.jaxpr_sync_findings(clean.jaxpr) == []


def test_hlo_pass_catches_callbacks_infeed_outfeed():
    hlo = ('  %cc.1 = f32[] custom-call(f32[] %x), '
           'custom_call_target="xla_python_cpu_callback"\n'
           '  %if.2 = ((f32[8]{0}), token[]) infeed(token[] %tok)\n'
           '  %of.3 = token[] outfeed(f32[8]{0} %y, token[] %tok)\n')
    codes = {f.code for f in sync_lint.hlo_sync_findings(hlo)}
    assert codes == {"hlo_callback", "hlo_infeed", "hlo_outfeed"}
    assert sync_lint.hlo_sync_findings(
        "  %add.1 = f32[] add(f32[] %a, f32[] %b)\n") == []


# ---------------------------------------------------------------------------
# pass 2: donation / retrace


def _sgd_hlo(donate):
    n = 1 << 18  # f32[262144] = 1 MiB

    def step(p, x):
        return p - 0.1 * x, (p * x).sum()

    jitted = jax.jit(step, donate_argnums=(0,) if donate else ())
    return jitted.lower(jnp.ones(n), jnp.ones(n)).compile().as_text()


def test_non_donated_param_buffer_is_a_pointed_error():
    hlo = _sgd_hlo(donate=False)
    fs = donation_lint.donation_findings(hlo, min_bytes=1 << 20)
    errs = [f for f in fs if f.severity == "error"]
    assert errs and errs[0].code == "non_donated"
    assert "not donated" in errs[0].message
    assert donation_lint.first_nondonated(hlo) is not None


def test_donated_param_passes_and_batch_is_info_only():
    hlo = _sgd_hlo(donate=True)
    assert donation_lint.parse_donated_params(hlo) == {0}
    assert donation_lint.first_nondonated(hlo) is None
    # param 1 (the "batch") is large but shape-unmatched: info only
    fs = donation_lint.donation_findings(hlo, min_bytes=1 << 20)
    assert {f.severity for f in fs} <= {"info"}
    summ = donation_lint.donation_summary(hlo)
    assert summ["donated"] == 1 and summ["donated_bytes"] == 1 << 20


def test_entry_parse_on_committed_corpus():
    with open(os.path.join(ROOT, "tests", "data", "hlo_corpus",
                           "tuple_sync.txt")) as f:
        params, outputs = donation_lint.parse_entry_shapes(f.read())
    assert [p[1:] for p in params] == [("f32", "128"), ("f32", "64")]
    assert outputs == [("f32", "128"), ("f32", "64")]


def test_retrace_detected_when_cache_grows():
    jitted = jax.jit(lambda x: x + 1)
    jitted(jnp.ones(4))
    (f,) = donation_lint.retrace_findings(jitted, max_traces=1)
    assert f.code == "retrace_ok"
    jitted(jnp.ones(8))  # second shape -> second trace
    (f,) = donation_lint.retrace_findings(jitted, max_traces=1)
    assert f.code == "retrace" and f.severity == "error"


# ---------------------------------------------------------------------------
# exemption policy


def test_exemption_without_reason_is_a_config_error(tmp_path):
    p = tmp_path / "e.json"
    p.write_text(json.dumps(
        {"exemptions": [{"id": "sync:float:m.py:fit:float",
                         "reason": "  "}]}))
    with pytest.raises(ValueError, match="no reason"):
        load_exemptions(str(p))
    p.write_text(json.dumps({"exemptions": [
        {"id": "a:b:c", "reason": "x"}, {"id": "a:b:c", "reason": "y"}]}))
    with pytest.raises(ValueError, match="duplicate"):
        load_exemptions(str(p))


def test_wildcard_exemptions_and_unused_detection():
    fs = [Finding("sync", "device_get", "error",
                  "m.py:fit:device_get", "msg"),
          Finding("donation", "retrace", "error", "step:cache", "msg")]
    fs, unused = apply_exemptions(fs, {
        "sync:device_get:*": "recovery boundary",
        "predicted:inconsistent:nmt": "stale"})
    assert fs[0].exempted and fs[0].reason == "recovery boundary"
    assert not fs[1].exempted
    assert unused == ["predicted:inconsistent:nmt"]
    tally = counts(fs)
    assert tally == {"error": 1, "warning": 0, "info": 0, "exempted": 1}


def test_repo_exemption_file_loads_and_every_entry_has_reason():
    ex = load_exemptions(os.path.join(
        ROOT, "flexflow_tpu", "verify", "exemptions.json"))
    assert ex and all(r.strip() for r in ex.values())


# ---------------------------------------------------------------------------
# pass 3: predicted-time grounded accept (unit rules)

_GROUP8 = [list(range(8))]


def _rec(nbytes, op="all-reduce", cross=True, groups=None):
    return {"op": op, "bytes": float(nbytes), "cross": cross,
            "groups": _GROUP8 if groups is None else groups,
            "async": False}


def _audit(searched_mb, dp_mb):
    return {"searched_collectives": [_rec(searched_mb * 1e6)],
            "dp_collectives": [_rec(dp_mb * 1e6)],
            "searched_cross_bytes": searched_mb * 1e6,
            "dp_cross_bytes": dp_mb * 1e6}


def test_predicted_time_consistent_when_comm_funds_the_win():
    topo = Topology(devices_per_ici_group=4)
    v = audit_consistent_time(_audit(1.0, 100.0), 1.5, topo)
    assert v["mode"] == "time" and v["consistent"]
    assert v["searched_pred_s"] < v["dp_pred_s"]


def test_predicted_time_rejects_comm_inflated_plan():
    """The deliberately comm-inflated plan: compiled collectives cost
    MORE predicted seconds than DP while claiming a 1.5x win ->
    REJECTED (the transformer_2x4 falsification class)."""
    topo = Topology(devices_per_ici_group=4)
    v = audit_consistent_time(_audit(100.0, 1.0), 1.5, topo)
    assert v["mode"] == "time" and not v["consistent"]


def test_predicted_time_win_must_be_funded_by_comm_saving():
    topo = Topology(devices_per_ici_group=4)
    a = _audit(90.0, 100.0)          # saves a sliver of comm time
    # the sliver cannot fund a claimed 2.0x win of 10 simulated seconds
    v = audit_consistent_time(a, 2.0, topo, dp_time_s=20.0,
                              best_time_s=10.0)
    assert not v["consistent"] and v["claimed_win_s"] == 10.0
    # a tiny claimed win IS funded by the same saving
    d, s = v["dp_pred_s"], v["searched_pred_s"]
    v2 = audit_consistent_time(a, 1.3, topo, dp_time_s=1.0,
                               best_time_s=1.0 - (d - s))
    assert v2["consistent"]


def test_predicted_time_no_win_claim_tolerates_parity():
    topo = Topology(devices_per_ici_group=4)
    assert audit_consistent_time(_audit(50.0, 50.0), 1.0,
                                 topo)["consistent"]
    assert not audit_consistent_time(_audit(80.0, 50.0), 1.0,
                                     topo)["consistent"]


def test_predicted_time_falls_back_to_bytes_without_records():
    a = _audit(1.0, 100.0)
    a["dp_collectives"] = None       # legacy (cross, intra) dp_known
    v = audit_consistent_time(a, 1.5, Topology(devices_per_ici_group=4))
    assert v["mode"] == "bytes" and v["consistent"]


# ---------------------------------------------------------------------------
# pass 3 end-to-end: NMT and pipeline paths on the virtual mesh

_NMT_OVERRIDES = {"batch_size": 8, "hidden_size": 32, "embed_size": 32,
                  "vocab_size": 256,
                  # keep chunks_per_seq == 2 (the op names in
                  # nmt_8dev.json) while unrolling 2 LSTM steps per
                  # chunk instead of 10 — same graph shape, 5x less
                  # compile work
                  "seq_length": 4, "lstm_per_node_length": 2}
_TLM_OVERRIDES = {"batch_size": 8, "seq_length": 16, "num_layers": 2,
                  "d_model": 32, "num_heads": 4, "d_ff": 64,
                  "vocab_size": 128}


def test_nmt_strategy_audits_in_predicted_time(machine8):
    audit = audit_in_process(
        "nmt", 8, 4, os.path.join(ROOT, "examples", "strategies",
                                  "nmt_8dev.json"),
        overrides=_NMT_OVERRIDES)
    assert audit["searched_collectives"] is not None
    assert audit["dp_collectives"] is not None
    v = audit_consistent_time(audit, 1.0,
                              Topology(devices_per_ici_group=4))
    assert v["mode"] == "time"
    assert v["searched_pred_s"] > 0 and v["dp_pred_s"] > 0


def test_pipeline_block_strategy_lowers_and_audits(machine8, tmp_path):
    """A strategy carrying an accepted __pipeline__ block builds the
    SAME PipelinedLM the lm driver runs and its compiled collectives go
    through the predicted-time audit (VERDICT: the pipeline wins
    carried no compiled-HLO audit)."""
    from flexflow_tpu.strategy import Strategy

    s = Strategy()
    s.pipeline = {"stages": 2, "microbatches": 2, "tp": 1}
    path = str(tmp_path / "pp.json")
    s.save(path)
    audit = audit_in_process("transformer", 8, 4, path,
                             dp_known=(0.0, 0.0),
                             overrides=_TLM_OVERRIDES)
    recs = audit["searched_collectives"]
    assert recs, "pipelined program must contain collectives"
    # the stage handoff lowers to cross-group traffic on a 2x4 topology
    assert any(r["cross"] for r in recs)
    assert audit["searched_pred_s"] > 0


def test_pipeline_grounded_accept_rejects_inflated_block(monkeypatch,
                                                         machine8):
    """_pipeline_grounded_accept vetoes a block whose compiled
    collectives eat the claimed win, and keeps one within budget."""
    from flexflow_tpu.apps import search as app_search
    from flexflow_tpu.strategy import Strategy
    from flexflow_tpu.utils import hlo_audit

    pp = {"best": {"stages": 2, "microbatches": 4, "tp": 1},
          "candidates": [{"stages": 2, "microbatches": 4, "tp": 1,
                          "time_s": 0.8, "comm_s": 1e-4,
                          "tp_comm_s": 0.0, "param_sync_s": 5e-5}],
          "reference_time_s": 1.0}
    opts = {"model": "transformer", "batch_size": None,
            "dtype": "float32"}
    calls = {}

    def fake_audit(model, devices, ici, path, *a, **kw):
        calls["strategy"] = Strategy.load(path)
        return {"searched_collectives": [_rec(calls["nbytes"])]}

    monkeypatch.setattr(hlo_audit, "audit_subprocess", fake_audit)
    calls["nbytes"] = 100e9          # inflated: ~seconds of comm
    ok, detail = app_search._pipeline_grounded_accept(
        opts, machine8, Strategy(), pp, log=lambda *a: None)
    assert not ok and not detail["consistent"]
    assert detail["plan"] == "pipeline" and detail["stages"] == 2
    assert calls["strategy"].pipeline == pp["best"]
    calls["nbytes"] = 100            # trivially within budget
    ok, detail = app_search._pipeline_grounded_accept(
        opts, machine8, Strategy(), pp, log=lambda *a: None)
    assert ok and detail["compiled_pred_s"] <= \
        detail["modeled_comm_s"] + 0.5 * detail["claimed_win_s"]


# ---------------------------------------------------------------------------
# lint CLI + obs record + report rendering


def test_lint_cli_source_only_json(capsys):
    from flexflow_tpu.apps import lint

    rc = lint.main(["--source-only", "--json"])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["error"] == 0
    assert rec["exempted"] >= 5      # model.py's approved sync-ok sites


def test_lint_cli_full_pass_on_small_transformer(tmp_path, capsys,
                                                 machine8):
    """End-to-end: source/jaxpr/HLO sync + donation/retrace passes on a
    small pipelined transformer, emitting the lint obs record; exit 0
    and the record is rendered by the report.  (--skip-predicted: the
    predicted pass re-lowers searched AND DP programs — it has its own
    end-to-end coverage above and in ``make lint``.)"""
    from flexflow_tpu.apps import lint
    from flexflow_tpu.obs import read_events, report

    from flexflow_tpu.strategy import Strategy

    s = Strategy()
    s.pipeline = {"stages": 2, "microbatches": 2, "tp": 1}
    spath = str(tmp_path / "pp.json")
    s.save(spath)
    # the default exemption file is tuned to the make-lint (alexnet)
    # configuration; this small fully-donated model needs none
    epath = str(tmp_path / "exemptions.json")
    with open(epath, "w") as f:
        json.dump({"exemptions": []}, f)
    rc = lint.main(["transformer", "--devices", "8", "--ici-group", "4",
                    "--strategy", spath, "--json", "--steps", "2",
                    "--overrides", json.dumps(_TLM_OVERRIDES),
                    "--exemptions", epath, "--skip-predicted",
                    "-obs-dir", str(tmp_path), "-run-id", "lintrun"])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0, rec
    assert rec["error"] == 0
    assert rec["donation"]["donated"] >= 1
    assert "predicted" not in rec
    events = list(read_events(str(tmp_path / "lintrun.jsonl")))
    assert [e["kind"] for e in events] == ["run_start", "lint"]
    text = report.render(events)
    assert "== lint ==" in text and "verifier[transformer]" in text
    assert report.summarize(events)["lint"]["error"] == 0


def test_report_renders_lint_and_obs_kind_coverage(tmp_path):
    """The lint record renders with findings + predicted verdict; the
    remaining emitted kinds (checkpoint_save, pipeline_candidate,
    pipeline_decision, elastic_refused, elastic_rejoin) pass through
    render() without falling into the unknown-kind bucket."""
    from flexflow_tpu.obs import RunLog, read_events, report

    path = str(tmp_path / "r.jsonl")
    with RunLog(path, run_id="r", surface="test") as ol:
        ol.event("lint", model="alexnet", error=1, warning=0, exempted=2,
                 findings=[{"severity": "error", "pass_name": "sync",
                            "code": "device_get",
                            "message": "m.py:5: per-step device_get"}],
                 predicted={"searched_pred_s": 1e-3, "dp_pred_s": 2e-3,
                            "mode": "time", "consistent": True})
        ol.event("checkpoint_save", step=1, path="ck")
        ol.event("pipeline_candidate", stages=2, microbatches=4,
                 time_s=0.5)
        ol.event("pipeline_decision", accepted=True, stages=2)
        ol.event("elastic_refused", reason="below min_devices")
        ol.event("elastic_rejoin", hosts=2)
    events = list(read_events(path))
    text = report.render(events)
    assert "== lint ==" in text
    assert "1 error(s)" in text and "device_get" in text
    assert "CONSISTENT" in text
    assert "unknown kind" not in text.lower()
    assert report.summarize(events)["lint"]["error"] == 1


# ---------------------------------------------------------------------------
# repo checker tools stay green


@pytest.mark.parametrize("tool", ["check_obs_kinds.py", "repo_lint.py"])
def test_checker_tool_green_on_repo(tool):
    p = subprocess.run([sys.executable, os.path.join(ROOT, "tools", tool)],
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stdout + p.stderr
    assert " ok" in p.stdout
