"""Elastic re-expansion + graceful drain + step watchdog (the round that
closes the shrink-only gap): machine.grow, boundary-piggybacked regrow
probes -> recover_grow, preempt drain with the exit-0 contract,
StepWatchdog hang detection, the windowed transient-retry refill, and
the idempotent release/uninstall paths — plus report/metrics coverage
for the new record kinds (device_return, preempt, step_hang)."""

import math
import os
import subprocess
import sys
import threading

import numpy as np
import pytest


from flexflow_tpu.config import FFConfig
from flexflow_tpu.model import FFModel
from flexflow_tpu.utils import elastic

BATCH = 24  # divisible by the 8-, 6- and 4-device meshes


def _build(cfg, machine):
    ff = FFModel(cfg, machine)
    img = ff.create_input((cfg.batch_size, 16, 16, 3), name="image")
    t = ff.conv2d("conv1", img, 8, 3, 3, 1, 1, 1, 1, relu=True)
    t = ff.flat("flat", t)
    t = ff.linear("fc", t, 8, relu=False)
    ff.softmax("softmax", t)
    return ff


def _host_batches(seed=3, n=4, batch=BATCH):
    rng = np.random.RandomState(seed)
    ring = [(rng.randn(batch, 16, 16, 3).astype("float32"),
             rng.randint(0, 8, (batch,)).astype("int32"))
            for _ in range(n)]
    i = 0
    while True:
        yield ring[i % n]
        i += 1


def _cfg(tmp_path=None, **kw):
    base = dict(batch_size=BATCH, input_height=16, input_width=16,
                num_iterations=8, print_freq=2, num_classes=8, seed=3)
    if tmp_path is not None:
        base["obs_dir"] = str(tmp_path / "obs")
        base["run_id"] = "lifecycle"
    base.update(kw)
    return FFConfig(**base)


def _events(out):
    from flexflow_tpu import obs

    return list(obs.read_run(out["obs_path"]))


def _no_watchdog_threads():
    return [t.name for t in threading.enumerate()
            if t.name.startswith("ff-step-watchdog")] == []


# ---------------------------------------------------------------------------
# parsing + flags


def test_parse_round9_fault_kinds():
    from flexflow_tpu.utils.faultinject import KINDS, parse_fault_spec

    for k in ("device_return", "preempt", "step_hang"):
        assert k in KINDS
    out = parse_fault_spec("device_return@2,preempt@5,step_hang@3x2")
    assert out == {"device_return": [(2, 1)], "preempt": [(5, 1)],
                   "step_hang": [(3, 2)]}
    cfg = FFConfig.from_args(
        ["--max-regrows", "2", "--regrow-probes", "3",
         "--drain-budget-s", "7.5", "--hang-factor", "4.0",
         "--hang-min-s", "1.5", "--transient-reset-steps", "8"])
    assert cfg.max_regrows == 2 and cfg.regrow_probes == 3
    assert cfg.drain_budget_s == 7.5
    assert cfg.hang_factor == 4.0 and cfg.hang_min_s == 1.5
    assert cfg.transient_reset_steps == 8
    from flexflow_tpu.apps.lm import parse_args as lm_parse
    from flexflow_tpu.apps.nmt import parse_args as nmt_parse

    for parse in (lm_parse, nmt_parse):
        c = parse(["--max-regrows", "2", "--regrow-probes", "3",
                   "--drain-budget-s", "7.5", "--hang-factor", "4.0",
                   "--hang-min-s", "1.5",
                   "--transient-reset-steps", "8"])
        assert c.max_regrows == 2 and c.regrow_probes == 3
        assert c.drain_budget_s == 7.5 and c.hang_factor == 4.0
        assert c.hang_min_s == 1.5 and c.transient_reset_steps == 8


# ---------------------------------------------------------------------------
# machine.grow + regrow probing (units)


def test_machine_grow_validation(machine8):
    m6 = machine8.shrink([0, 1, 2, 3, 4, 5])
    back = m6.grow(machine8.devices[6:8])
    assert back.num_devices == 8
    assert back.devices == machine8.devices  # canonical id order
    assert m6.num_devices == 6  # never mutated
    with pytest.raises(ValueError):
        m6.grow([])
    with pytest.raises(ValueError):
        m6.grow([machine8.devices[0]])  # already in the machine
    with pytest.raises(ValueError):
        m6.grow([machine8.devices[6], machine8.devices[6]])  # dup


def test_regrow_context_and_probe_streak(machine8):
    sig = elastic.DeviceLossDetected(dead=[6, 7], step=4, losses=(),
                                     injected=True)
    model = _build(_cfg(), machine8)
    ctx = elastic.make_regrow_context(model, sig, probes_needed=2)
    assert len(ctx["dead"]) == 2 and ctx["k"] == 2
    assert all(is_inj for _, is_inj in ctx["dead"])

    class Inj:  # fires device_return on the 2nd probe
        enabled = True

        def __init__(self):
            self.n = 0

        def fire(self, kind, site=""):
            assert kind == "device_return"
            self.n += 1
            return self.n == 2

    inj = Inj()
    log = lambda *a: None
    assert not elastic.probe_regrow(ctx, inj=inj, log=log)  # miss
    assert not elastic.probe_regrow(ctx, inj=inj, log=log)  # streak 1
    assert elastic.probe_regrow(ctx, inj=inj, log=log)      # streak 2
    assert ctx["probes"] == 3

    # REAL dead devices: a probe failure resets the streak (flapping)
    ctx2 = {"dead": [(machine8.devices[7], False)], "healthy": 0,
            "probes": 0, "k": 2, "answering": False}
    flaky = {"n": 0}

    def probe(dev):
        flaky["n"] += 1
        if flaky["n"] == 2:
            raise RuntimeError("flap")

    assert not elastic.probe_regrow(ctx2, probe=probe, log=log)
    assert ctx2["healthy"] == 1
    assert not elastic.probe_regrow(ctx2, probe=probe, log=log)
    assert ctx2["healthy"] == 0  # flap reset the streak
    assert not elastic.probe_regrow(ctx2, probe=probe, log=log)
    assert elastic.probe_regrow(ctx2, probe=probe, log=log)


# ---------------------------------------------------------------------------
# fit-loop integration: full lifecycle, regrow cap, drain, watchdog


@pytest.mark.filterwarnings("ignore")
def test_full_lifecycle_shrink_then_grow(machine8, tmp_path):
    cfg = _cfg(tmp_path, num_iterations=12, elastic=True, min_devices=2,
               regrow_probes=2, max_regrows=1,
               research_budget_s=5.0,
               fault_spec="device_loss@3x2,device_return@2")
    out = _build(cfg, machine8).fit(_host_batches(),
                                    log=lambda *a: None,
                                    rebuild=_build)
    assert len(out["loss"]) == 12
    assert all(math.isfinite(l) for l in out["loss"])
    assert out["elastic_resizes"] == 2
    assert out["devices"] == 8  # grew back
    events = _events(out)
    resizes = [e for e in events if e["kind"] == "elastic_resize"]
    assert [r.get("direction") for r in resizes] == ["shrink", "grow"]
    assert resizes[1]["from_devices"] == 6
    assert resizes[1]["to_devices"] == 8
    assert resizes[1]["migration"] == "in_memory"
    rets = [e for e in events if e["kind"] == "device_return"]
    assert len(rets) == 1 and rets[0]["returned"] == [6, 7]
    kinds = [e["kind"] for e in events]
    assert kinds.index("device_return") < kinds.index("elastic_resize",
                                                      kinds.index(
                                                          "device_return"))


@pytest.mark.filterwarnings("ignore")
def test_max_regrows_zero_stays_shrunk(machine8, tmp_path):
    cfg = _cfg(tmp_path, num_iterations=8, elastic=True, min_devices=2,
               max_regrows=0, research_budget_s=5.0,
               fault_spec="device_loss@3x2,device_return@1")
    out = _build(cfg, machine8).fit(_host_batches(),
                                    log=lambda *a: None,
                                    rebuild=_build)
    assert len(out["loss"]) == 8
    assert out["elastic_resizes"] == 1
    assert out["devices"] == 6  # expansion capped out
    events = _events(out)
    assert not [e for e in events if e["kind"] == "device_return"]
    # no regrow probes were taken at all (the context is never armed)
    assert not [e for e in events if e["kind"] == "device_probe"
                and e.get("needed") is not None]


@pytest.mark.filterwarnings("ignore")
def test_preempt_drain_and_resume(machine8, tmp_path):
    from flexflow_tpu.utils import checkpoint as ckpt

    ckpt_dir = str(tmp_path / "ckpt")
    base = _build(_cfg(print_freq=0), machine8).fit(
        _host_batches(), log=lambda *a: None)["loss"]

    cfg = _cfg(tmp_path, ckpt_dir=ckpt_dir, ckpt_freq=2,
               drain_budget_s=30.0, fault_spec="preempt@3")
    out = _build(cfg, machine8).fit(_host_batches(),
                                    log=lambda *a: None)
    assert out["drained"] and out["completed_steps"] == 4
    assert out["drain"]["ckpt_step"] == 4
    assert out["drain"]["mode"] in ("boundary_save", "sync", "async")
    last = ckpt.latest_step(ckpt_dir)
    ok, why = ckpt.verify_checkpoint(ckpt_dir, last)
    assert last == 4 and ok, why
    events = _events(out)
    drains = [e for e in events if e["kind"] == "preempt_drain"]
    assert len(drains) == 1 and drains[0]["step"] == 4
    assert [float(l) for l in out["loss"]] == \
        [float(l) for l in base[:4]]

    # a fresh run over the same --ckpt-dir resumes and loses nothing
    out2 = _build(_cfg(ckpt_dir=ckpt_dir, ckpt_freq=2, print_freq=0),
                  machine8).fit(_host_batches(), log=lambda *a: None)
    assert "drained" not in out2
    assert [float(l) for l in out2["loss"]] == \
        [float(l) for l in base[4:]]


@pytest.mark.filterwarnings("ignore")
def test_preempt_drain_without_ckpt_dir(machine8, tmp_path):
    cfg = _cfg(tmp_path, fault_spec="preempt@3")
    out = _build(cfg, machine8).fit(_host_batches(),
                                    log=lambda *a: None)
    assert out["drained"] and out["completed_steps"] == 4
    assert out["drain"]["mode"] == "none"
    assert out["drain"]["ckpt_step"] is None


def test_step_watchdog_unit():
    from flexflow_tpu.utils.health import StepWatchdog

    wd = StepWatchdog(0.0)
    assert not wd.enabled  # default off: no timer threads, ever

    events = []

    class OLog:
        enabled = True

        def event(self, kind, **kw):
            events.append((kind, kw))

    wd = StepWatchdog(2.0, min_deadline_s=0.15, olog=OLog(),
                      log=lambda *a: None)
    for _ in range(4):
        wd.observe(0.01)
    assert wd.step_estimate_s() == pytest.approx(0.01)
    assert wd.deadline_s() == pytest.approx(0.15)  # floor dominates

    wd.arm(5)
    assert wd.disarm() is None  # healthy boundary: timer cancelled
    wd.arm(6)
    wd.stall(margin_s=0.25)  # sleeps past the deadline -> expiry
    info = wd.disarm()
    assert info is not None and info["step"] == 6
    assert wd.hangs == 1
    assert events and events[0][0] == "step_hang"
    assert events[0][1]["deadline_s"] == pytest.approx(0.15)
    wd.close()
    assert _no_watchdog_threads()


@pytest.mark.filterwarnings("ignore")
def test_watchdog_transient_hang_continues(machine8, tmp_path):
    cfg = _cfg(tmp_path, num_iterations=6, elastic=True,
               hang_factor=1.0, hang_min_s=0.2,
               fault_spec="step_hang@2")
    out = _build(cfg, machine8).fit(_host_batches(),
                                    log=lambda *a: None)
    assert len(out["loss"]) == 6  # healthy probes -> run continues
    events = _events(out)
    hangs = [e for e in events if e["kind"] == "step_hang"]
    assert len(hangs) == 1 and hangs[0]["step"] == 2
    trans = [e for e in events if e["kind"] == "device_loss"
             and e.get("source") == "watchdog"]
    assert len(trans) == 1
    assert trans[0]["classification"] == "transient"
    assert _no_watchdog_threads()

    # without --elastic an expired watchdog is a loud failure
    cfg2 = _cfg(num_iterations=6, hang_factor=1.0, hang_min_s=0.2,
                fault_spec="step_hang@2")
    with pytest.raises(elastic.DeviceLostError,
                       match="watchdog deadline"):
        _build(cfg2, machine8).fit(_host_batches(), log=lambda *a: None)
    assert _no_watchdog_threads()


@pytest.mark.filterwarnings("ignore")
def test_watchdog_permanent_hang_recovers(machine8, tmp_path,
                                          monkeypatch):
    # the wedged boundary probes PERMANENTLY dead -> shrink recovery
    real_probe = elastic.probe_devices

    def probe(machine, olog=None, **kw):
        if machine.num_devices == 8:
            return [0, 1, 2, 3, 4, 5], [6, 7], []
        return real_probe(machine, olog=olog, **kw)

    monkeypatch.setattr(elastic, "probe_devices", probe)
    cfg = _cfg(tmp_path, num_iterations=8, elastic=True, min_devices=2,
               max_regrows=0, hang_factor=1.0, hang_min_s=0.2,
               research_budget_s=5.0, fault_spec="step_hang@3")
    out = _build(cfg, machine8).fit(_host_batches(),
                                    log=lambda *a: None,
                                    rebuild=_build)
    assert len(out["loss"]) == 8
    assert out["elastic_resizes"] == 1 and out["devices"] == 6
    events = _events(out)
    kinds = [e["kind"] for e in events]
    # the stall converts into recovery: step_hang BEFORE the resize
    assert kinds.index("step_hang") < kinds.index("elastic_resize")
    rz = next(e for e in events if e["kind"] == "elastic_resize")
    assert rz["direction"] == "shrink" and rz["migration"] == "in_memory"
    assert _no_watchdog_threads()


# ---------------------------------------------------------------------------
# windowed transient-retry refill


class XlaRuntimeError(RuntimeError):
    """classify() keys on the TYPE NAME jax raises, so the injected
    flake must carry it."""


def _flaky_model(cfg, machine, fail_steps):
    ff = _build(cfg, machine)
    real = ff.make_train_step()
    st = {"done": 0, "failed": set()}

    def step(params, state, opt, *batch):
        nxt = st["done"] + 1
        if nxt in fail_steps and nxt not in st["failed"]:
            st["failed"].add(nxt)
            raise XlaRuntimeError("device unavailable (injected flake)")
        out = real(params, state, opt, *batch)
        st["done"] += 1
        return out

    step.lower = real.lower  # fit's post-loop records analyse the step
    ff.make_train_step = lambda: step
    return ff


@pytest.mark.filterwarnings("ignore")
def test_transient_window_refills_budget(machine8, tmp_path):
    # spread-out hiccups: each is followed by >= transient_reset_steps
    # healthy steps, so the budget refills and the run completes
    cfg = _cfg(tmp_path, num_iterations=10, elastic=True,
               transient_reset_steps=1)
    out = _flaky_model(cfg, machine8, {2, 4, 6, 8}).fit(
        _host_batches(), log=lambda *a: None)
    assert len(out["loss"]) == 10
    events = _events(out)
    refills = [e for e in events if e["kind"] == "recovery"
               and e.get("after") == "transient_window"]
    assert len(refills) >= 2
    trans = [e for e in events if e["kind"] == "device_loss"
             and e.get("classification") == "transient"]
    assert len(trans) == 4


@pytest.mark.filterwarnings("ignore")
def test_transient_budget_exhausts_without_window(machine8):
    # window disabled (0): the budget never refills, the 4th hiccup is
    # a persistent failure even though every probe is healthy
    cfg = _cfg(num_iterations=10, elastic=True, transient_reset_steps=0)
    with pytest.raises(XlaRuntimeError, match="device unavailable"):
        _flaky_model(cfg, machine8, {2, 3, 4, 5}).fit(
            _host_batches(), log=lambda *a: None)


# ---------------------------------------------------------------------------
# idempotent release / uninstall


def test_release_idempotent_and_reentrant():
    from flexflow_tpu import distributed

    saved = distributed._STATE["initialized"]
    try:
        distributed._STATE["initialized"] = True
        results = []
        threads = [threading.Thread(
            target=lambda: results.append(distributed.release()))
            for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results.count(True) == 1  # exactly one did the teardown
        assert distributed.release() is False  # idempotent afterwards
    finally:
        distributed._STATE["initialized"] = saved


def test_installers_restore_idempotent():
    from flexflow_tpu.utils import faultinject

    inj = faultinject.FaultInjector("preempt@1")
    restore = faultinject.install_scoped(inj)
    assert faultinject.get() is inj
    assert restore() is True
    assert restore() is False  # re-entrant no-op
    assert faultinject.get() is not inj

    drain = {}
    restore_sig = elastic.install_drain_handler(drain,
                                                log=lambda *a: None)
    try:
        assert drain["requested"] is False
        elastic.request_drain(drain)  # real signal path when installed
        assert drain["requested"] is True
        import signal

        assert drain["signum"] == int(signal.SIGTERM)
    finally:
        assert restore_sig() is True
    assert restore_sig() is False  # idempotent

    # flag-only fallback (handler not installed)
    d2 = {"requested": False, "signum": None}
    elastic.request_drain(d2)
    assert d2["requested"] is True


# ---------------------------------------------------------------------------
# observability: report / summarize / metrics / consistency


def test_report_and_summarize_new_kinds():
    from flexflow_tpu.obs.report import _misc_section, render, summarize

    events = [
        {"kind": "run_start", "run": "r"},
        {"kind": "step_hang", "step": 4, "deadline_s": 1.5,
         "estimate_s": 0.1, "factor": 4.0},
        {"kind": "device_probe", "outcome": "answering", "devices": [7],
         "healthy_streak": 2, "needed": 2, "probe": 3},
        {"kind": "device_return", "step": 6, "returned": [7],
         "from_devices": 7, "to_devices": 8, "probes": 3},
        {"kind": "elastic_resize", "direction": "grow", "step": 6,
         "from_devices": 7, "to_devices": 8, "research_s": 0.1,
         "migration": "in_memory", "regrid_bytes": 10, "regrid_hops": 1,
         "steps_lost": 0},
        {"kind": "preempt_drain", "step": 9, "steps_completed": 9,
         "ckpt_step": 8, "signal": 15, "seconds": 0.2, "budget_s": 60.0,
         "mode": "async"},
    ]
    text = render(events)
    assert "step_hang at step 4" in text
    assert "device_return at step 6" in text
    assert "elastic_resize[grow]" in text
    assert "preempt_drain at step 9" in text
    # the elastic section owns the new kinds — never double-rendered
    assert _misc_section(events) == []

    s = summarize(events)
    el = s["elastic"]
    assert el["counts"]["step_hang"] == 1
    assert el["counts"]["device_return"] == 1
    assert el["counts"]["preempt_drain"] == 1
    assert el["resizes"][0]["direction"] == "grow"
    assert el["step_hangs"][0]["step"] == 4
    assert el["device_returns"][0]["returned"] == [7]
    assert el["preempt_drain"]["mode"] == "async"
    # direction inferred from device counts when the record lacks it
    s2 = summarize([{"kind": "elastic_resize", "step": 2,
                     "from_devices": 8, "to_devices": 6}])
    assert s2["elastic"]["resizes"][0]["direction"] == "shrink"


def test_metrics_labeled_export(tmp_path):
    from flexflow_tpu.obs import metrics

    path = str(tmp_path / "m.prom")
    ex = metrics.MetricsExporter(path)
    ex.update(elastic_events=3, drain_pending=1.0)
    ex.update_labeled("elastic_events", {"direction": "shrink"}, 2)
    ex.update_labeled("elastic_events", {"direction": "grow"}, 1)
    ex.write()
    flat = metrics.read_textfile(path)
    assert flat["elastic_events"] == 3.0  # plain total unchanged
    assert flat["drain_pending"] == 1.0
    lab = metrics.read_labeled(path)
    assert lab["elastic_events"]['direction="shrink"'] == 2.0
    assert lab["elastic_events"]['direction="grow"'] == 1.0


def test_ckpt_corrupt_injection_caught_by_verify(tmp_path):
    # the coverage gap the consistency check exposed: ckpt_corrupt had
    # docs but no test.  One injected bit-flip in the committed
    # arrays.npz must fail digest verification.
    from flexflow_tpu.utils import checkpoint as ckpt
    from flexflow_tpu.utils import faultinject

    d = str(tmp_path / "ck")
    tree = {"fc": {"w": np.ones((4, 4), "float32")}}
    restore = faultinject.install_scoped(
        faultinject.FaultInjector("ckpt_corrupt@2"))
    try:
        ckpt.save_checkpoint(d, 1, tree, {}, {})
        ckpt.save_checkpoint(d, 2, tree, {}, {})  # this one corrupted
    finally:
        restore()
    ok1, _ = ckpt.verify_checkpoint(d, 1)
    ok2, why = ckpt.verify_checkpoint(d, 2)
    assert ok1 and not ok2, why


def test_fault_kind_consistency_check(tmp_path):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(root, "tools", "check_fault_kinds.py")
    proc = subprocess.run([sys.executable, script], capture_output=True,
                          text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "check_fault_kinds ok" in proc.stdout

    # negative: a declared kind with no docs and no tests must fail
    (tmp_path / "flexflow_tpu" / "utils").mkdir(parents=True)
    (tmp_path / "tests").mkdir()
    (tmp_path / "flexflow_tpu" / "utils" / "faultinject.py").write_text(
        'KINDS = ("loss_nan", "made_up_kind")\n')
    (tmp_path / "README.md").write_text("| `loss_nan` | step | x |\n")
    (tmp_path / "tests" / "test_x.py").write_text("loss_nan\n")
    proc = subprocess.run([sys.executable, script, str(tmp_path)],
                          capture_output=True, text=True)
    assert proc.returncode == 1
    assert "made_up_kind" in proc.stdout
