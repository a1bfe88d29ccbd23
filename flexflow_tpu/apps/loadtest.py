"""Sustained-load serving harness — the serving trajectory pin.

    python -m flexflow_tpu.apps.loadtest --out SERVE_r01.json
    python -m flexflow_tpu.apps.loadtest --smoke

Drives the seeded load generator's composable arrival patterns
(``diurnal``/``bursty``/``heavy_tail``, '+'-composed; serve/loadgen.py)
through the continuous-batching engine at a sweep of device counts and
pins the resulting p50/p99/TTFT/TPOT/QPS/goodput-under-SLO curve the
way ``bench.py`` pins training throughput.

The sweep holds the virtual per-step service time constant and scales
the decode rectangle with the mesh (``--slots-per-device`` slots per
device), so fewer devices means fewer concurrent decode slots, queueing
delay, and honest latency degradation — all in VIRTUAL time, so every
number in the artifact is bit-reproducible under ``--seed`` (wall_s
fields are informational and excluded from the committed JSON).

Per sweep point the harness evaluates the latency SLO (obs/slo.py
burn-rate over the point's ``serve_request`` stream) and emits one
``loadtest`` + one ``slo`` obs record; after the sweep it exports and
validates the per-request Perfetto trace (obs/trace.py
``serve_trace_events``).

stdout carries EXACTLY ONE JSON line in the bench metric-line shape —

    {"metric": "gpt_tiny_serve_qps_8dev", "value": ..., "unit":
     "req/s", "vs_baseline": ..., ...}

where ``vs_baseline`` is the largest sweep point's goodput QPS over the
smallest's (the device-scaling payoff).  ``--out`` additionally writes
the ``serve_bench_v1`` artifact (committed as ``SERVE_r01.json``) with
the metric line under ``"parsed"`` and the full per-point sweep table.
``make loadtest-smoke`` asserts the line's shape, finiteness, and that
the trace validated.

``--chaos SPEC`` (implies ``--disagg``) replays the same seeded sweep
with a FRESH deterministic fault injector per point (the
utils/faultinject.py occurrence grammar) against the router's full
resilience stack — bounded retries, KV re-materialization, SLO-burn
shedding.  The artifact (committed as ``SERVE_r03.json``) gains
per-point recovery counters and a ``vs_r02`` block proving bounded
degradation: at every point ``completed + unserved + shed + failed ==
offered`` — zero silently-lost requests under injected chaos.
"""

from __future__ import annotations

import json
import math
import os
import sys


def _err(*a, **kw):
    print(*a, file=sys.stderr, **kw)
    sys.stderr.flush()


def parse_args(argv):
    from flexflow_tpu.utils.flags import flag_stream

    opts = {
        "requests": 60, "rate_qps": 80.0, "pattern": "diurnal+bursty",
        "devices": "2,4,8", "slots_per_device": 2, "seed": 0,
        "prompt_len": 4, "max_new_tokens": 3, "step_time_s": 0.0,
        "slo_target_s": 0.25, "availability": 0.95, "slo_window_s": 2.0,
        "percentile": 99.0, "out": "", "trace": "", "obs_dir": "",
        "run_id": "", "metrics_path": "", "smoke": False,
        "disagg": False, "baseline": "", "chaos": "",
    }
    for a, val in flag_stream(list(argv)):
        if a in ("-n", "--requests"):
            opts["requests"] = int(val())
        elif a == "--rate-qps":
            opts["rate_qps"] = float(val())
        elif a == "--pattern":
            opts["pattern"] = val()
        elif a == "--devices":
            opts["devices"] = val()
        elif a == "--slots-per-device":
            opts["slots_per_device"] = int(val())
        elif a == "--seed":
            opts["seed"] = int(val())
        elif a == "--prompt-len":
            opts["prompt_len"] = int(val())
        elif a == "--max-new-tokens":
            opts["max_new_tokens"] = int(val())
        elif a == "--step-time-s":
            opts["step_time_s"] = float(val())
        elif a == "--slo-target-s":
            opts["slo_target_s"] = float(val())
        elif a == "--availability":
            opts["availability"] = float(val())
        elif a == "--slo-window-s":
            opts["slo_window_s"] = float(val())
        elif a == "--percentile":
            opts["percentile"] = float(val())
        elif a in ("-o", "--out"):
            opts["out"] = val()
        elif a == "--trace":
            opts["trace"] = val()
        elif a in ("-obs-dir", "--obs-dir"):
            opts["obs_dir"] = val()
        elif a in ("-run-id", "--run-id"):
            opts["run_id"] = val()
        elif a in ("-metrics-path", "--metrics-path"):
            opts["metrics_path"] = val()
        elif a == "--disagg":
            opts["disagg"] = True
        elif a == "--chaos":
            # a utils/faultinject.py occurrence spec (e.g.
            # "replica_crash@3,handoff_drop@5"), replayed FRESH at
            # every sweep point against the --disagg router with the
            # resilience stack armed; implies --disagg
            opts["chaos"] = val()
            opts["disagg"] = True
        elif a == "--baseline":
            opts["baseline"] = val()
        elif a == "--smoke":
            opts["smoke"] = True
    if opts["smoke"]:
        opts["requests"] = min(opts["requests"], 18)
    return opts


def _round(v, nd=6):
    """Stable rounding for the committed artifact: virtual-time floats
    are bit-deterministic, rounding just keeps the JSON diff-friendly.
    None passes through; non-finite values are preserved (the smoke
    asserts finiteness separately)."""
    if v is None or not isinstance(v, float):
        return v
    return round(v, nd) if math.isfinite(v) else v


def _disagg_carve(devices: int) -> dict:
    """Deterministic prefill/decode split of a ``devices``-wide sweep
    point: half the mesh prefils (two replicas once it is >= 4 devices
    wide), the rest decodes as one pool.  2 -> 1p/1d, 4 -> 2p/2d,
    8 -> 2x2p/4d."""
    prefill_devices = max(1, devices // 2)
    decode_devices = max(1, devices - prefill_devices)
    prefill_replicas = 2 if prefill_devices >= 4 else 1
    return {
        "prefill_devices": prefill_devices,
        "decode_devices": decode_devices,
        "prefill_replicas": prefill_replicas,
        "per_replica_devices": prefill_devices // prefill_replicas,
    }


def _disagg_router(machine, devices, opts, olog, metrics, log):
    """The sweep point's disaggregated serving stack: prefill replicas
    on their own device slices (full forward per step) and one decode
    pool whose virtual step is scaled by the analytic single-token
    ratio (sim/search.decode_step_ratio) — the perf mechanism the
    artifact measures.  Returns (router, carve, decode_step_ratio)."""
    from flexflow_tpu.apps.serve import _build_lm
    from flexflow_tpu.serve.engine import DEFAULT_STEP_TIME_S, ServeEngine
    from flexflow_tpu.serve.router import AdmissionGate, ServeRouter
    from flexflow_tpu.sim.search import decode_step_ratio
    from flexflow_tpu.utils.retry import RetryPolicy

    carve = _disagg_carve(devices)
    base_step = opts["step_time_s"] or DEFAULT_STEP_TIME_S
    prefill = []
    for j in range(carve["prefill_replicas"]):
        per = carve["per_replica_devices"]
        m = machine.shrink(list(range(j * per, (j + 1) * per)))
        pbatch = max(1, opts["slots_per_device"] * per)
        model, _ = _build_lm(m, batch=pbatch, seed=opts["seed"],
                             tiny=True, research_budget_s=0.5)
        prefill.append(ServeEngine(
            model, None, olog=olog, metrics=metrics, log=log,
            step_time_s=base_step, phase="prefill"))
    dm = machine.shrink(list(range(carve["prefill_devices"], devices)))
    dbatch = max(1, opts["slots_per_device"] * carve["decode_devices"])
    dmodel, _ = _build_lm(dm, batch=dbatch, seed=opts["seed"],
                          tiny=True, research_budget_s=0.5)
    ratio = decode_step_ratio(dmodel)
    decode = [ServeEngine(dmodel, None, olog=olog, metrics=metrics,
                          log=log, step_time_s=base_step * ratio,
                          phase="decode")]
    kw = {}
    if opts.get("chaos"):
        # the chaos sweep arms the full resilience stack: bounded
        # seeded retries plus the SLO-burn admission gate built from
        # the same SLO the sweep evaluates
        kw = dict(retry_policy=RetryPolicy(),
                  admission=AdmissionGate(
                      latency_target_s=opts["slo_target_s"],
                      availability=opts["availability"],
                      window_s=opts["slo_window_s"]))
    return (ServeRouter(prefill, decode, olog=olog, metrics=metrics,
                        log=log, **kw), carve, ratio)


def _sweep_point(machine, devices, opts, olog, metrics, log) -> dict:
    """One sweep point: build the tiny GPT with ``slots_per_device *
    devices`` decode slots on a ``devices``-wide mesh, serve the SAME
    seeded patterned request stream, evaluate the SLO.  Under
    ``--disagg`` the same mesh is instead carved into prefill replicas
    + a decode pool behind the router (serve/router.py)."""
    from flexflow_tpu.apps.serve import _build_lm
    from flexflow_tpu.obs.slo import SLOSpec, evaluate, log_record
    from flexflow_tpu.serve.engine import ServeEngine
    from flexflow_tpu.serve.loadgen import patterned_requests

    m = machine if devices >= machine.num_devices \
        else machine.shrink(list(range(devices)))
    batch = max(1, opts["slots_per_device"] * devices)
    carve = ratio = None
    if opts["disagg"]:
        router, carve, ratio = _disagg_router(machine, devices, opts,
                                              olog, metrics, log)
        seq = int(router.decode[0].model._inputs[0].shape[1])
        vocab = router.decode[0].model.t.vocab_size
    else:
        model, _ = _build_lm(m, batch=batch, seed=opts["seed"],
                             tiny=True, research_budget_s=0.5)
        engine = ServeEngine(model, None, olog=olog, metrics=metrics,
                             log=log,
                             step_time_s=opts["step_time_s"] or None)
        seq = int(model._inputs[0].shape[1])
        vocab = model.t.vocab_size
    reqs = patterned_requests(
        opts["requests"], seed=opts["seed"], rate_qps=opts["rate_qps"],
        pattern=opts["pattern"], vocab_size=vocab,
        prompt_len=opts["prompt_len"],
        max_new_tokens=opts["max_new_tokens"],
        max_prompt_len=max(opts["prompt_len"],
                           seq - opts["max_new_tokens"] - 1))
    # unique rids across sweep points so the merged obs stream's
    # per-request trace lanes stay distinct
    for i, r in enumerate(reqs):
        r.rid = devices * 100000 + i
    inj = None
    if opts["disagg"] and opts.get("chaos"):
        # a FRESH injector per sweep point: every point replays the
        # same occurrence-indexed fault schedule, so the whole sweep
        # is bit-reproducible under --seed + --chaos
        from flexflow_tpu.utils.faultinject import (FaultInjector,
                                                    install_scoped)

        inj = FaultInjector(opts["chaos"], olog=olog)
        restore = install_scoped(inj)
        try:
            summary = router.run(reqs)
        finally:
            restore()
    else:
        summary = router.run(reqs) if opts["disagg"] \
            else engine.run(reqs)

    spec = SLOSpec(name=f"p{opts['percentile']:g}-"
                        f"{opts['slo_target_s']:g}s",
                   latency_target_s=opts["slo_target_s"],
                   percentile=opts["percentile"],
                   availability=opts["availability"],
                   window_s=opts["slo_window_s"])
    point_events = [{"kind": "serve_request", "done_v": r.done_v,
                     "latency_s": r.latency_s}
                    for r in reqs if r.done_v is not None]
    slo = evaluate(point_events, spec)
    log_record(olog, dict(slo, devices=devices))

    last_arrival = max(r.arrival_v for r in reqs) if reqs else 0.0
    point = {
        "devices": devices,
        "slots": batch,
        "requests": summary["requests"],
        "completed": summary["completed"],
        "unserved": summary["unserved"],
        "qps": summary["qps"],
        "offered_qps": (len(reqs) / last_arrival)
        if last_arrival > 0 else 0.0,
        "p50_s": summary["p50_s"],
        "p99_s": summary["p99_s"],
        "ttft_p50_s": summary["ttft_p50_s"],
        "ttft_p99_s": summary["ttft_p99_s"],
        "tpot_p50_s": summary["tpot_p50_s"],
        "tpot_p99_s": summary["tpot_p99_s"],
        "goodput_qps": slo["goodput_qps"],
        "slo_burn_rate": slo["burn_rate"],
        "slo_max_window_burn_rate": slo["max_window_burn_rate"],
        "slo_compliant": slo["compliant"],
        "steps": summary["steps"],
        "virtual_s": summary["virtual_s"],
    }
    shape = f"{devices} device(s) x {batch} slots"
    if opts["disagg"]:
        point.update({
            "prefill_devices": carve["prefill_devices"],
            "prefill_replicas": carve["prefill_replicas"],
            "decode_devices": carve["decode_devices"],
            "decode_step_ratio": ratio,
            "handoffs": summary["handoffs"],
            "affinity_hits": summary["affinity_hits"],
            "kv_refetches": summary["kv_refetches"],
        })
        shape = (f"{devices} device(s) "
                 f"[{carve['prefill_replicas']}x"
                 f"{carve['per_replica_devices']}dev prefill + "
                 f"{carve['decode_devices']}dev decode, "
                 f"step ratio {ratio:.3f}]")
    if inj is not None:
        accounted = summary["completed"] + summary["unserved"] \
            + summary["shed"] + summary["failed"]
        point.update({
            "offered": len(reqs),
            "shed": summary["shed"],
            "failed": summary["failed"],
            "retries": summary["retries"],
            "kv_rebuilds": summary["kv_rebuilds"],
            "replica_downs": summary["replica_down"],
            "replicas_live": summary["replicas_live"],
            "faults_fired": inj.fired(),
            "recovery": {k: {kk: _round(vv) for kk, vv in d.items()}
                         for k, d in summary["recovery"].items()},
        })
        assert accounted == summary["requests"] == len(reqs), \
            (f"silent request loss at {devices} device(s): "
             f"{accounted} accounted of {len(reqs)} offered "
             f"({summary})")
        shape += (f" + chaos ({inj.fired()} fault(s): "
                  f"{summary['replica_down']} down, "
                  f"{summary['retries']} retries, "
                  f"{summary['kv_rebuilds']} rebuilds, "
                  f"{summary['shed']} shed, "
                  f"{summary['failed']} failed)")
    olog.event("loadtest", pattern=opts["pattern"],
               rate_qps=opts["rate_qps"], seed=opts["seed"], **point)
    log(f"loadtest: {shape} -> "
        f"qps {point['qps']:.1f}, p50 {point['p50_s'] * 1e3:.0f} ms, "
        f"p99 {point['p99_s'] * 1e3:.0f} ms, ttft p50 "
        f"{point['ttft_p50_s'] * 1e3:.0f} ms, goodput "
        f"{point['goodput_qps']:.1f} qps "
        f"(burn {point['slo_burn_rate']:.2f}x)")
    return point


def _write_trace(opts, olog, log) -> bool:
    """Export + validate the sweep's per-request Perfetto lanes.
    Returns True when the trace validated (and was written)."""
    from flexflow_tpu import obs
    from flexflow_tpu.obs import trace as obstrace

    if not olog.enabled:
        return False
    events = list(obs.read_run(olog.path))
    trace = obstrace.chrome_trace(obstrace.serve_trace_events(events))
    errors = obstrace.validate_trace(trace)
    if errors:
        for e in errors:
            log(f"loadtest trace INVALID: {e}")
        return False
    path = opts["trace"] or os.path.join(
        os.path.dirname(olog.path), "serve.trace.json")
    obstrace.write_trace(path, trace)
    opts["trace"] = path
    log(f"loadtest trace ok: {path} "
        f"({len(trace['traceEvents'])} events)")
    return True


def _vs_baseline_artifact(sweep, path, log):
    """Per-device-count deltas of a ``--disagg`` sweep against a
    committed single-pool artifact (SERVE_r01.json): same seed, same
    traffic spec, so the TTFT-p99 speedup and goodput ratio at each
    shared device count isolate the disaggregation win.  Returns None
    (and logs) when the baseline artifact is missing."""
    if not path or not os.path.exists(path):
        log(f"loadtest: baseline artifact {path or '<unset>'} not "
            f"found — vs_r01 omitted")
        return None
    with open(path) as f:
        base = json.load(f)
    by_dev = {int(p["devices"]): p for p in base.get("sweep", [])
              if p.get("devices")}
    points = {}
    for p in sweep:
        b = by_dev.get(int(p["devices"]))
        if b is None:
            continue
        entry = {}
        for k in ("ttft_p99_s", "p99_s", "goodput_qps",
                  "slo_compliant"):
            entry[f"{k}_r01"] = b.get(k)
            entry[f"{k}_r02"] = _round(p.get(k))
        if b.get("ttft_p99_s") and p.get("ttft_p99_s"):
            entry["ttft_p99_speedup"] = _round(
                b["ttft_p99_s"] / p["ttft_p99_s"], 4)
        if b.get("goodput_qps") and p.get("goodput_qps"):
            entry["goodput_ratio"] = _round(
                p["goodput_qps"] / b["goodput_qps"], 4)
        points[str(p["devices"])] = entry
    return {"baseline": os.path.basename(path),
            "baseline_schema": base.get("schema"),
            "points": points}


def _vs_chaos_baseline(sweep, path, log):
    """The bounded-degradation proof of a ``--chaos`` sweep against the
    fault-free ``--disagg`` artifact (SERVE_r02.json): same seed, same
    traffic, same carve, so at every shared device count the block pins
    (1) the accounting invariant — ``completed + unserved + shed +
    failed == offered``, every admitted request either finished, was
    explicitly refused at the door, or explicitly failed its retry
    budget; NOTHING silently lost — and (2) how far goodput/p99
    degraded from the fault-free run.  Returns None (and logs) when the
    baseline artifact is missing."""
    if not path or not os.path.exists(path):
        log(f"loadtest: chaos baseline artifact {path or '<unset>'} "
            f"not found — vs_r02 omitted")
        return None
    with open(path) as f:
        base = json.load(f)
    by_dev = {int(p["devices"]): p for p in base.get("sweep", [])
              if p.get("devices")}
    points = {}
    for p in sweep:
        accounted = p["completed"] + p["unserved"] + p["shed"] \
            + p["failed"]
        entry = {
            "offered": p["offered"],
            "accounted": accounted,
            "no_silent_loss": accounted == p["offered"],
            "completed": p["completed"],
            "unserved": p["unserved"],
            "shed": p["shed"],
            "failed": p["failed"],
            "retries": p["retries"],
            "kv_rebuilds": p["kv_rebuilds"],
            "replica_downs": p["replica_downs"],
        }
        b = by_dev.get(int(p["devices"]))
        if b is not None:
            for k in ("completed", "goodput_qps", "p99_s",
                      "ttft_p99_s"):
                entry[f"{k}_r02"] = b.get(k)
                entry[f"{k}_r03"] = _round(p.get(k))
            if b.get("goodput_qps") and p.get("goodput_qps"):
                entry["goodput_ratio"] = _round(
                    p["goodput_qps"] / b["goodput_qps"], 4)
            if b.get("p99_s") and p.get("p99_s"):
                entry["p99_ratio"] = _round(p["p99_s"] / b["p99_s"], 4)
        points[str(p["devices"])] = entry
    return {"baseline": os.path.basename(path),
            "baseline_schema": base.get("schema"),
            "points": points}


def _repo_artifact(name: str) -> str:
    """A committed artifact, resolved from the CWD first (make runs at
    the repo root) then beside the package."""
    if os.path.exists(name):
        return name
    root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(root, name)


def _default_baseline() -> str:
    """The committed single-pool artifact (fault-free disagg sweeps
    compare against it)."""
    return _repo_artifact("SERVE_r01.json")


def run(opts, log=_err) -> dict:
    from flexflow_tpu.apps.serve import _olog_metrics
    from flexflow_tpu.machine import MachineModel

    machine = MachineModel()
    sweep_devices = sorted({int(d) for d in
                            str(opts["devices"]).split(",") if d.strip()})
    if not sweep_devices:
        raise SystemExit("loadtest: --devices must name at least one "
                         "device count")
    bad = [d for d in sweep_devices
           if d < 1 or d > machine.num_devices]
    if bad:
        raise SystemExit(f"loadtest: device counts {bad} outside the "
                         f"{machine.num_devices}-device mesh")

    olog, metrics = _olog_metrics(
        dict(opts, model="gpt-tiny"), surface="loadtest")
    sweep = [_sweep_point(machine, d, opts, olog, metrics, log)
             for d in sweep_devices]
    trace_ok = _write_trace(opts, olog, log)
    olog.close()

    base, top = sweep[0], sweep[-1]
    vs_baseline = (top["goodput_qps"] / base["goodput_qps"]) \
        if base["goodput_qps"] > 0 else None
    kind = "chaos_serve" if opts["chaos"] \
        else ("disagg_serve" if opts["disagg"] else "serve")
    line = {
        "metric": f"gpt_tiny_{kind}_qps_{top['devices']}dev",
        "value": _round(top["qps"], 4),
        "unit": "req/s",
        "vs_baseline": _round(vs_baseline, 4),
        "run_id": olog.run_id if olog.enabled else None,
        "seed": opts["seed"],
        "pattern": opts["pattern"],
        "sweep_points": len(sweep),
        "p50_s": _round(top["p50_s"]),
        "p99_s": _round(top["p99_s"]),
        "ttft_p50_s": _round(top["ttft_p50_s"]),
        "ttft_p99_s": _round(top["ttft_p99_s"]),
        "tpot_p50_s": _round(top["tpot_p50_s"]),
        "burn_rate": _round(top["slo_burn_rate"]),
        "goodput_qps": _round(top["goodput_qps"]),
        "trace_validated": trace_ok,
        "trace": opts["trace"] or None,
    }
    artifact = {
        "schema": "serve_bench_v1",
        "seed": opts["seed"],
        "pattern": opts["pattern"],
        "requests_per_point": opts["requests"],
        "rate_qps": opts["rate_qps"],
        "max_new_tokens": opts["max_new_tokens"],
        "prompt_len": opts["prompt_len"],
        "slots_per_device": opts["slots_per_device"],
        "slo": {"latency_target_s": opts["slo_target_s"],
                "percentile": opts["percentile"],
                "availability": opts["availability"],
                "window_s": opts["slo_window_s"]},
        "parsed": {k: line[k] for k in
                   ("metric", "value", "unit", "vs_baseline")},
        "sweep": [{k: _round(v) for k, v in p.items()} for p in sweep],
    }
    if opts["chaos"]:
        artifact["disagg"] = True
        artifact["chaos"] = opts["chaos"]
        vs_r02 = _vs_chaos_baseline(
            sweep, opts["baseline"] or _repo_artifact("SERVE_r02.json"),
            log)
        if vs_r02 is not None:
            artifact["vs_r02"] = vs_r02
            line["vs_r02"] = {d: e.get("goodput_ratio")
                              for d, e in vs_r02["points"].items()}
    elif opts["disagg"]:
        artifact["disagg"] = True
        vs_r01 = _vs_baseline_artifact(
            sweep, opts["baseline"] or _default_baseline(), log)
        if vs_r01 is not None:
            artifact["vs_r01"] = vs_r01
            line["vs_r01"] = {d: e.get("ttft_p99_speedup")
                              for d, e in vs_r01["points"].items()}
    if opts["out"]:
        with open(opts["out"], "w") as f:
            json.dump(artifact, f, indent=1)
            f.write("\n")
        log(f"loadtest artifact: {opts['out']}")
        line["out"] = opts["out"]
    return {"line": line, "artifact": artifact}


def main(argv=None, log=_err) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    opts = parse_args(argv)
    import jax

    jax.config.update("jax_platforms", "cpu")
    if jax.device_count() < 2:
        raise SystemExit(
            f"loadtest needs the multi-device simulated mesh "
            f"(XLA_FLAGS=--xla_force_host_platform_device_count=8), "
            f"got {jax.device_count()} device(s)")
    if not opts["obs_dir"]:
        import tempfile

        with tempfile.TemporaryDirectory(prefix="ff-loadtest-") as td:
            opts["obs_dir"] = os.path.join(td, "obs")
            result = run(opts, log)
            print(json.dumps(result["line"]))
            return 0
    result = run(opts, log)
    print(json.dumps(result["line"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
