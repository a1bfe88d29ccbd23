"""Simulator calibration against the real chip (VERDICT r2 #4).

The reference simulator self-reports its dpCompTime on the machine it was
built on (scripts/simulator.cc:117, 1424); round 2 never compared our
simulator's DP prediction with the chip it claims to model.  This driver
closes that: for each model at its bench shape it

  1. times the REAL jitted DP train step on the local chip (the bench
     protocol: chained steps, one host sync);
  2. asks the simulator for its DP prediction under the analytic roofline
     and under MeasuredCostModel (per-op shard timings in the SAME compute
     dtype, protocol v3);
  3. writes examples/strategies/calibration.json with the ratios.

tests/test_calibration.py asserts the committed measured-model ratios stay
within +-30%.  Run on the TPU host:

    python -m flexflow_tpu.apps.calibrate -o examples/strategies/calibration.json

``--from-obs DIR`` is the drift-driven recalibration path (no probe run,
no chip access needed beyond the training that already happened): it
consumes the obs records real runs accumulated — measured per-op
``op_time`` records (fit's sampled op-timing mode), the simulated per-op
times of the strategies those runs trained under (``sim_trace`` /
``search_breakdown``), and the step-level ``sim_drift`` gauges — and
refits the two knob families the simulator already exposes:

  * per-kind anchor ratios (measured/simulated per op kind, median) —
    the ``kind_anchors`` seed ``MeasuredCostModel(anchors_path=...)``
    loads, so unmeasurable candidates rank on the observed scale;
  * collective constants: the step-time residual the anchored compute
    does not explain is attributed to communication and folded into
    ``dcn_bandwidth``/``dcn_latency`` — the exact keys
    ``Topology.from_calibration`` reads (clamped to 10x either way).
    When the stream carries a ``step_budget`` record (obs/budget.py),
    its input-stall / host-sync / checkpoint buckets are subtracted
    first, so non-communication overheads stop polluting the comm
    constants (compute-only anchors).

    python -m flexflow_tpu.apps.calibrate --from-obs runs/ -o recal.json
"""

from __future__ import annotations

import json
import os
import sys
import time


def _real_cnn_step(model: str, batch: int, dtype: str):
    import bench  # repo-root bench.py — the timed-loop protocol lives there

    per_chip, tput, elapsed, _, _, _ = bench.run(
        model=model, batch_size=batch, dtype=dtype,
        windows=3)  # calibration wants a stable point, not the full spread
    return batch / tput  # seconds per step (tput is machine-wide)


def _real_nmt_step(dtype: str):
    from flexflow_tpu.machine import MachineModel
    from flexflow_tpu.nmt.rnn_model import (RnnConfig, RnnModel,
                                            synthetic_token_batches)

    machine = MachineModel()
    cfg = RnnConfig(compute_dtype=dtype)
    model = RnnModel(cfg, machine)
    data = synthetic_token_batches(machine, cfg.batch_size, cfg.seq_length,
                                   cfg.vocab_size)
    params, state = model.init()
    opt = model.init_opt_state(params)
    step = model.make_train_step()
    batch = next(data)
    import jax

    for _ in range(3):
        params, state, opt, loss = step(params, state, opt, *batch)
    jax.block_until_ready(loss)  # warm-up fence
    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        params, state, opt, loss = step(params, state, opt, *batch)
    jax.block_until_ready(loss)  # closes the timed window
    return (time.perf_counter() - t0) / iters, model


def _build_cnn(model: str, batch: int, machine, dtype: str):
    from flexflow_tpu.config import FFConfig

    if model == "inception":
        from flexflow_tpu.models.inception import build_inception_v3 as b
        size = 299
    else:
        from flexflow_tpu.models.alexnet import build_alexnet as b
        size = 224
    cfg = FFConfig(batch_size=batch, input_height=size, input_width=size,
                   compute_dtype=dtype)
    return b(cfg, machine)


def calibrate(out: str = "", log=print) -> dict:
    from flexflow_tpu.machine import MachineModel
    from flexflow_tpu.sim.cost_model import (AnalyticCostModel,
                                             MeasuredCostModel)
    from flexflow_tpu.sim.search import StrategySearch

    cache = os.path.join(os.path.dirname(os.path.abspath(out))
                         if out else ".", ".costcache_v3.json")
    machine = MachineModel()
    configs = [
        ("alexnet", 1024, "bfloat16"),
        ("inception", 256, "bfloat16"),
        ("nmt", 64, "bfloat16"),
    ]
    results = {}
    for name, batch, dtype in configs:
        if name == "nmt":
            real_s, model = _real_nmt_step(dtype)
        else:
            real_s = _real_cnn_step(name, batch, dtype)
            model = _build_cnn(name, batch, machine, dtype)
        row = {"batch_size": batch, "dtype": dtype,
               "measured_step_s": round(real_s, 6)}
        for cm_name, cm in (
                ("analytic", AnalyticCostModel()),
                ("measured", MeasuredCostModel(cache_path=cache,
                                               dtype=dtype))):
            search = StrategySearch(model, machine, cost_model=cm)
            pred = search.simulate(search.dp_assignment())
            row[f"predicted_{cm_name}_s"] = round(pred, 6)
            row[f"ratio_{cm_name}"] = round(pred / real_s, 4)
        results[name] = row
        log(f"{name}: real {real_s*1e3:.2f} ms/step, "
            f"analytic {row['ratio_analytic']}x, "
            f"measured {row['ratio_measured']}x")
    payload = {
        "chip": str(machine.devices[0]),
        "protocol": "bench timed loop vs StrategySearch.simulate(dp); "
                    "MeasuredCostModel v3 shard timings in the step dtype",
        "models": results,
    }
    if out:
        with open(out, "w") as f:
            json.dump(payload, f, indent=1)
        log(f"written to {out}")
    return payload


def _median(values):
    values = sorted(values)
    return values[len(values) // 2] if values else None


def calibrate_from_obs(obs_dir: str, out: str = "", log=print) -> dict:
    """Refit cost-model knobs from accumulated obs records (the
    drift-driven recalibration loop — ROADMAP item, closed here).  Reads
    every ``*.jsonl`` stream (rotated parts included) under ``obs_dir``;
    see the module docstring for what is fitted.  The artifact is dual-
    consumable: ``MeasuredCostModel(anchors_path=...)`` reads
    ``kind_anchors``, ``Topology.from_calibration`` reads
    ``dcn_bandwidth``/``dcn_latency``."""
    import re

    from flexflow_tpu.machine import Topology
    from flexflow_tpu.obs import read_events
    from flexflow_tpu.obs.trace import real_op_seconds, sim_op_seconds

    events = []
    names = sorted(fn for fn in os.listdir(obs_dir)
                   if fn.endswith(".jsonl")
                   or re.search(r"\.jsonl\.\d+$", fn))
    for fn in names:
        events.extend(read_events(os.path.join(obs_dir, fn)))
    sim_ops = sim_op_seconds(events)
    real_ops = real_op_seconds(events)
    drifts = [e for e in events if e.get("kind") == "sim_drift"]
    # per-kind anchors: measured / simulated-compute, median per kind.
    # The compute part is the comparable quantity — the isolated op_time
    # harness cannot see in-op collectives, so anchoring against
    # compute_s + collective_s would fold comm error into compute knobs.
    by_kind = {}
    joined = 0
    for op in set(sim_ops) & set(real_ops):
        kind = sim_ops[op].get("op_kind") or real_ops[op].get("op_kind")
        base = sim_ops[op].get("compute_s", sim_ops[op]["seconds"])
        if not real_ops[op].get("measured", True):
            continue  # analytic stand-in: a real/analytic anchor of
            #           exactly 1.0 would be circular, not informative
        if not kind or not base or base <= 0:
            continue
        joined += 1
        by_kind.setdefault(str(kind), []).append(
            real_ops[op]["seconds"] / base)
    anchors = {k: round(_median(v), 4) for k, v in sorted(by_kind.items())}
    # collective constants: the measured step time minus the ANCHORED
    # compute (and the assignment-invariant optimizer stream) is the
    # communication budget the run actually paid; its ratio to the
    # simulated collective seconds rescales the DCN constants.  Clamped —
    # a residual outside 10x means the attribution itself is suspect.
    #
    # Compute-only discipline (MFU-waterfall round): when the stream
    # carries a ``step_budget`` record, the non-communication overheads
    # it already attributed — input stall, host-sync boundaries,
    # checkpoint I/O — are subtracted from the measured step BEFORE the
    # residual is blamed on collectives, so a stalled input pipeline or
    # a chatty checkpoint cadence no longer masquerades as slow DCN and
    # pollutes the comm constants.
    comm_scale = None
    breakdowns = [e for e in events if e.get("kind") == "search_breakdown"]
    budgets = [e for e in events if e.get("kind") == "step_budget"]
    measured_step = _median([float(d["measured_s"]) for d in drifts
                             if d.get("measured_s")])
    budget_excluded = {}
    if budgets:
        bk = budgets[-1].get("buckets") or {}
        budget_excluded = {
            k: float(bk.get(k, 0.0) or 0.0)
            for k in ("input_stall", "host_sync", "checkpoint")
            if bk.get(k)}
    excluded_s = sum(budget_excluded.values())
    if breakdowns and measured_step:
        bd = breakdowns[-1]
        anchored_compute = sum(
            float(r.get("compute_s", 0.0))
            * anchors.get(str(r.get("kind")), 1.0)
            for r in bd.get("ops", []))
        sim_comm = sum(float(r.get("collective_s", 0.0))
                       for r in bd.get("ops", []))
        opt_s = float(bd.get("opt_stream_s", 0.0))
        residual = measured_step - anchored_compute - opt_s - excluded_s
        if sim_comm > 0 and residual > 0:
            comm_scale = min(max(residual / sim_comm, 0.1), 10.0)
    base_topo = Topology()
    payload = {
        "source": "obs",
        "obs_dir": os.path.abspath(obs_dir),
        "streams": len(names),
        "records": len(events),
        "joined_ops": joined,
        "sim_drift": {"n": len(drifts),
                      "median_ratio": _median(
                          [float(d["value"]) for d in drifts
                           if d.get("value")])},
        "kind_anchors": anchors,
        "collective_scale": round(comm_scale, 4) if comm_scale else None,
        "dcn_bandwidth": base_topo.dcn_bandwidth / (comm_scale or 1.0),
        "dcn_latency": base_topo.dcn_latency * (comm_scale or 1.0),
        # the step_budget buckets excluded from the collective residual
        # (compute-only discipline); empty = no budget record, legacy fit
        "budget_excluded": {k: round(v, 6)
                            for k, v in budget_excluded.items()},
        "budget_excluded_s": round(excluded_s, 6),
    }
    for k, v in anchors.items():
        log(f"anchor {k}: x{v} (n={len(by_kind[k])})")
    if excluded_s:
        log(f"step_budget exclusions: {excluded_s * 1e3:.3f} ms/step "
            f"({', '.join(sorted(budget_excluded))}) kept out of the "
            f"collective residual")
    if comm_scale:
        log(f"collective residual scale: x{comm_scale:.3f} -> "
            f"dcn_bandwidth {payload['dcn_bandwidth']:.3e} B/s")
    elif drifts:
        log("collective constants unchanged (no positive residual or no "
            "search_breakdown in the streams)")
    if not anchors and not drifts:
        log("warning: no op_time/sim_drift records found — run fit() "
            "with -obs-dir and --op-time-every N first")
    if out:
        with open(out, "w") as f:
            json.dump(payload, f, indent=1)
        log(f"written to {out}")
    return payload


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    out = ""
    from_obs = ""
    from flexflow_tpu.utils.flags import flag_stream

    for a, val in flag_stream(argv):
        if a in ("-o", "--out"):
            out = val()
        elif a == "--from-obs":
            from_obs = val()
    if from_obs:
        calibrate_from_obs(from_obs, out)
    else:
        calibrate(out)


if __name__ == "__main__":
    from flexflow_tpu.utils.chip import enable_compile_cache

    enable_compile_cache()  # before the first compile
    main()
