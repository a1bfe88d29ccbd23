"""Benchmark entry point — prints EXACTLY ONE JSON line on stdout:
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
 "run_id": ..., "obs_path": ...}

Stdout hygiene: everything else (logging, JAX/absl warnings, any library
print) is routed to stderr, so the consuming harness parses stdout
directly instead of grepping the metric out of mixed tail text.  The full
bench record is also appended to the obs event stream (run-telemetry
JSONL; dir from $BENCH_OBS_DIR, default ``.obs/`` next to this file) and
its run-id + path ride in the metric line; render with
``python -m flexflow_tpu.apps.report``.

Flagship benchmark: Inception-v3 (the BASELINE.json north-star model;
reference topology inception.h / cnn.cc:191-214) training throughput per
chip on the local TPU, synthetic data (reference parity: the cnn.cc:110-128
timed loop printing images/s).  The reference publishes no absolute numbers
(BASELINE.md), so vs_baseline is the speedup of the benched strategy over
our own pure-data-parallel run on identical hardware — the reference's
headline metric (strategy vs DP).  Pass a strategy file as argv[1] to bench
it; with no strategy the benched config IS pure DP, so vs_baseline = 1.0 by
definition (no second run is made).  BENCH_MODEL=alexnet switches to the
AlexNet sanity config (batch 1024; single-chip saturation knee).

The number is a device measurement: on any platform but ``tpu`` the run
refuses (non-zero exit, no metric line), and the line names the platform,
``device_kind`` and device count it measured on.  ``--cpu-rehearsal`` (an
argument, never a default or an environment variable) runs the same code
pinned to the CPU for the schema check of ``make bench-smoke``: the
metric is then named ``cpu_rehearsal_*`` and carries no utilization.
"""

import json
import os
import sys
import time


def run(model="inception", batch_size=None, iters=10, warmup=3,
        dtype="bfloat16", strategy_file=None, windows=5,
        param_dtype="float32", placed_overlap="on", rehearsal=False):
    """Returns (per_chip, tput, elapsed, mfu, spread, extras) — ``extras``
    carries the device the run measured on, the execution-performance
    gauges the round-6 prongs add (``input_stall_s``: prefetch residual
    over the timed windows; the regrid plan accounting) and the
    compiled-program account.  Refuses a platform other than ``tpu``
    unless ``rehearsal``."""
    import jax

    from flexflow_tpu.utils.chip import max_memory_stat, require_tpu

    device = require_tpu("bench.py", rehearsal)

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.data import synthetic_batches
    from flexflow_tpu.machine import MachineModel

    if model == "inception":
        from flexflow_tpu.models.inception import build_inception_v3 as build
        size, batch_size = 299, batch_size or 256
    elif model == "alexnet":
        from flexflow_tpu.models.alexnet import build_alexnet as build
        size, batch_size = 224, batch_size or 1024
    else:
        raise SystemExit(f"unknown BENCH_MODEL {model!r} "
                         f"(expected 'inception' or 'alexnet')")

    machine = MachineModel()
    cfg = FFConfig(batch_size=batch_size, input_height=size, input_width=size,
                   num_iterations=iters, print_freq=0, compute_dtype=dtype,
                   param_dtype=param_dtype, placed_overlap=placed_overlap,
                   strategy_file=strategy_file or "")
    ff = build(cfg, machine)
    params, state = ff.init()
    opt_state = ff.init_opt_state(params)
    step = ff.make_train_step()
    data = synthetic_batches(machine, batch_size, size, size, mode="ones")
    # double-buffered device prefetch (data/prefetch.py): the bench pulls
    # through the same staging path fit() uses, and reports the residual
    # input stall the overlap could not hide
    from flexflow_tpu.data.prefetch import DevicePrefetcher

    data = DevicePrefetcher(data, machine=machine, depth=2)

    for _ in range(warmup):
        img, lbl = next(data)
        params, state, opt_state, loss = step(params, state, opt_state,
                                              img, lbl)
    # sync-ok: full sync (the steps form one dependency chain)
    jax.block_until_ready(loss)
    # Variance protocol (round 5, VERDICT r4 #2): a single timed window
    # made every per-round delta unfalsifiable.  Time ``windows``
    # independent windows of ``iters`` steps (each closed by a full
    # sync); report the MEDIAN and the observed spread.
    import statistics

    samples = []
    stall0 = data.stall_s
    for _ in range(max(windows, 1)):
        t0 = time.perf_counter()
        for i in range(iters):
            img, lbl = next(data)
            params, state, opt_state, loss = step(params, state, opt_state,
                                                  img, lbl)
        jax.block_until_ready(loss)  # sync-ok: closes the timed window
        samples.append(time.perf_counter() - t0)
    extras = {"device": device,
              "input_stall_s": round(data.stall_s - stall0, 6)}
    # top-level budget shares (MFU-waterfall round): how much of the
    # timed windows went to input stall (measured), and the simulator's
    # collective share for the benched assignment (the paper's per-op
    # cost model — labeled sim-derived by construction)
    total_timed = sum(samples)
    extras["stall_frac"] = round(extras["input_stall_s"] / total_timed, 6) \
        if total_timed > 0 else 0.0
    extras["comm_frac"] = 0.0
    from flexflow_tpu.sim.search import StrategySearch

    ss = StrategySearch(ff, machine=machine)
    asn = ss.assignment_for(cfg.strategies) if cfg.strategies \
        else ss.dp_assignment()
    sim_total = ss.simulate(asn)
    if sim_total > 0:
        extras["comm_frac"] = round(
            sum(r["collective_s"]
                for r in ss.cost_breakdown(asn)) / sim_total, 6)
    data.close()
    rsum = ff.regrid_plan_summary()
    if rsum:
        extras["regrid_hops"] = rsum["hops_after"]
        extras["regrid"] = rsum
    else:
        # single-device machines build no plan; the field still rides the
        # metric line so the harness schema is stable
        extras["regrid_hops"] = 0
    elapsed = statistics.median(samples)
    tput = iters * batch_size / elapsed
    per_chip = tput / machine.num_devices
    spread = {
        "windows": len(samples),
        "min": round(iters * batch_size / max(samples)
                     / machine.num_devices, 2),
        "max": round(iters * batch_size / min(samples)
                     / machine.num_devices, 2),
    }

    # MFU: FLOPs of the COMPILED step (post-fusion XLA cost analysis) over
    # elapsed time and whole-machine peak FLOPs — the pressure gauge
    # VERDICT r1 asked for (weak #7).  Lowering hits jit's cache.  The
    # peaks are those of the device_kind the run measured on (an unknown
    # kind raises); a rehearsal has no chip to be a fraction of.
    from flexflow_tpu.sim.cost_model import chip_perf
    from flexflow_tpu.utils.profiling import compiled_roofline

    perf = None if rehearsal else chip_perf(device["kind"])
    compiled = step.lower(params, state, opt_state, img, lbl).compile()
    rl = compiled_roofline(compiled, elapsed / iters, perf,
                           n_devices=machine.num_devices)
    mfu = rl.get("mxu_utilization")
    flops, bytes_ = rl["flops"], rl["bytes_accessed"]
    if perf is not None and flops > 0:
        # the roofline ceiling: the honest MFU upper bound of THIS
        # compiled program on this chip
        peak = perf.peak_flops * machine.num_devices
        hbm_bw = perf.hbm_bandwidth * machine.num_devices
        ceiling = flops / max(flops / peak, bytes_ / hbm_bw) / peak
        extras["mfu_ceiling"] = round(ceiling, 4)
        # of_ceiling (VERDICT item 6): fraction of THIS program's honest
        # roofline achieved — separates "the program is memory-bound"
        # from "we left time on the table" in a way raw MFU can't
        extras["of_ceiling"] = round(mfu / ceiling, 4)
    # compiled-program identity: line count + content hash of the
    # optimized HLO, so two metric lines are comparable at a glance
    # (same fingerprint = same program; an MFU move with a changed
    # fingerprint is a different compilation, not a runtime win)
    import hashlib

    hlo_text = compiled.as_text()
    extras["hlo_fingerprint"] = (
        f"{len(hlo_text.splitlines())}:"
        f"{hashlib.sha256(hlo_text.encode()).hexdigest()[:12]}")
    # donation account (round 13): bytes the step aliases in place,
    # straight from the executable's input_output_alias header — the
    # same ground truth the enforcing lint reads.  A donated_bytes
    # collapse between two metric lines means a buffer fell off the
    # donation path (and the lint will name it).
    from flexflow_tpu.verify.donation_lint import donation_summary

    extras["donated_bytes"] = donation_summary(hlo_text)["donated_bytes"]
    # the step's HBM footprint: the fullest device's runtime peak where
    # the backend reports one, else the compiled memory analysis
    # (arguments + outputs - aliased + temporaries)
    hbm_peak = max_memory_stat(machine.devices, "peak_bytes_in_use")
    if hbm_peak is None:
        mem = compiled.memory_analysis()
        hbm_peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                    - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    extras["hbm_peak_gb"] = round(hbm_peak / 1e9, 4)
    return per_chip, tput, elapsed, mfu, spread, extras


def main():
    import contextlib
    import logging

    # stdout hygiene: the metric line is the ONLY stdout byte this
    # process emits — logging and any library print go to stderr
    logging.basicConfig(stream=sys.stderr)
    real_stdout = sys.stdout
    with contextlib.redirect_stdout(sys.stderr):
        out = _bench_record()
    print(json.dumps(out), file=real_stdout)


def _bench_record():
    from flexflow_tpu.utils.chip import REHEARSAL_FLAG

    model = os.environ.get("BENCH_MODEL", "inception")
    args = [a for a in sys.argv[1:] if a != REHEARSAL_FLAG]
    rehearsal = len(args) != len(sys.argv) - 1
    strategy_file = args[0] if args else None
    # smoke knobs (make bench-smoke): shrink the config so the metric
    # line's SCHEMA — incl. the round-6 regrid_hops / input_stall_s
    # fields — is assertable on a laptop-class CPU rehearsal; unset = the
    # real protocol
    knobs = {}
    for env, key, cast in (("BENCH_BATCH", "batch_size", int),
                           ("BENCH_ITERS", "iters", int),
                           ("BENCH_WARMUP", "warmup", int),
                           ("BENCH_WINDOWS", "windows", int),
                           ("BENCH_DTYPE", "dtype", str),
                           ("BENCH_PARAM_DTYPE", "param_dtype", str),
                           ("BENCH_PLACED_OVERLAP", "placed_overlap", str)):
        if os.environ.get(env):
            knobs[key] = cast(os.environ[env])
    per_chip, tput, elapsed, mfu, spread, extras = run(
        model=model, strategy_file=strategy_file, rehearsal=rehearsal,
        **knobs)
    if strategy_file:
        dp_per_chip, _, _, _, _, _ = run(model=model, rehearsal=rehearsal,
                                         **knobs)
        vs_baseline = round(per_chip / dp_per_chip, 4)
    else:
        vs_baseline = 1.0  # benched config is itself the pure-DP baseline
    metric = (f"{model}_v3_train_throughput_per_chip"
              if model == "inception" else
              f"{model}_train_throughput_per_chip")
    out = {
        # a rehearsal's number is not a device measurement and must not
        # be found under the device metric's name
        "metric": f"cpu_rehearsal_{metric}" if rehearsal else metric,
        "value": round(per_chip, 2),
        "unit": "images/s/chip",
        "vs_baseline": vs_baseline,
        "spread": spread,
    }
    out.update(extras)
    # mixed-precision round: which precision/overlap policy this record
    # measured rides the metric line (runs are only comparable within a
    # policy)
    out["param_dtype"] = knobs.get("param_dtype", "float32")
    out["placed_overlap"] = knobs.get("placed_overlap", "on")
    if mfu is not None:
        out["mfu"] = round(mfu, 4)
    # round 13: share of the compute residual held by the fusion
    # auditor's top-3 rows, from the committed roofline profile for the
    # benched model.  A shrinking top-3 share with a flat residual means
    # the big levers were spent and the tail is next.
    repo = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(
            repo, "examples", "profiles",
            ("inception_v3" if model == "inception" else model)
            + "_roofline.json")) as f:
        profile = json.load(f)
    from flexflow_tpu.obs.fusions import residual_top_frac

    out["residual_top_frac"] = round(residual_top_frac(profile), 4)
    # the benched strategy's simulated timeline, when the search exported
    # one next to the artifact (apps/search.py -trace writes
    # <stem>.trace.json): its path rides the metric line so the harness
    # can hand sim + bench to `apps/report.py trace` without guessing
    if strategy_file:
        stem = os.path.splitext(strategy_file)[0]
        for cand in (stem + ".trace.json", strategy_file + ".trace.json"):
            if os.path.exists(cand):
                out["trace_path"] = cand
                break
    # Side report (VERDICT r1 #5): the searched strategy this bench would
    # exercise on a multi-chip machine, with its simulated speedup from the
    # committed search artifacts (examples/strategies/summary.json).
    with open(os.path.join(repo, "examples", "strategies",
                           "summary.json")) as f:
        summary = json.load(f)
    key = f"bench_{model}_8dev.json"
    if key in summary:
        out["searched_strategy"] = key
        out["simulated_speedup_vs_dp"] = summary[key]["speedup_vs_dp"]
    # bench surface of the obs subsystem: the full record also lands in
    # the run-telemetry JSONL, and its identity rides in the metric line
    from flexflow_tpu import obs as _obs

    obs_dir = os.environ.get("BENCH_OBS_DIR", os.path.join(repo, ".obs"))
    run_id = _obs.new_run_id()
    with _obs.RunLog(os.path.join(obs_dir, f"{run_id}.jsonl"),
                     run_id=run_id, surface="bench",
                     meta={"app": "bench", "model": model,
                           "strategy_file": strategy_file or ""}) as ol:
        ol.event("bench", **out)
        out["run_id"] = run_id
        out["obs_path"] = ol.path
    return out


if __name__ == "__main__":
    # first-ever run pays minutes of Inception compilation; later runs
    # that find the same cache directory start in seconds.  Set by the
    # CLI only — callers of main()/run() keep their jax config.
    from flexflow_tpu.utils.chip import enable_compile_cache

    enable_compile_cache()
    main()
