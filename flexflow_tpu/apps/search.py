"""Offline strategy search driver — reference executable parity
(scripts/simulator.cc main :1420-1472), with the loop the reference leaves
open closed: the found strategy is written to a strategy file the training
drivers consume directly (SURVEY.md §2.5 note).

    python -m flexflow_tpu.apps.search alexnet --devices 8 -o strat.json
    python -m flexflow_tpu.apps.search inception --devices 32 \
        --iters 250000 --measured -o strat.pb

``--devices N`` searches for an N-device machine regardless of local
hardware (the reference similarly models a 2x4 cluster from one box,
scripts/simulator.cc:32-33).  ``--measured`` times real per-op shard
computations on the local chip (scripts/cnn.h measure_* parity); default is
the analytic MXU/HBM roofline.  ``-o x.json`` writes JSON; any other
extension writes the reference-wire-compatible proto.

``-chains N`` runs N parallel Metropolis chains on native threads with
deterministic best-state exchange between chunks (chain 0 reproduces the
single-chain search for a fixed seed).  ``-delta on|off|check`` controls
the delta re-simulation: ``on`` (default) prices each proposal in
~O(affected ops), ``off`` pays a full re-simulation per proposal, and
``check`` cross-checks every delta against a full re-simulation, aborting
on divergence > 1e-9 (debug mode; the accepted sequence is identical in
all three for a fixed seed).

``--objective makespan|latency|decode`` picks what the simulator prices:
``makespan`` (default) is the full training step; ``latency`` prices ONE
forward/decode step from the same native tables (costs / 3, no gradient
sync, no optimizer stream) for serving-SLO search; ``decode`` prices a
SINGLE-TOKEN decode step (per-token forward plus each attention shard's
KV-cache HBM stream and sequence-shard collective) for the decode pool
of a disaggregated deployment.  ``--serve`` implies ``--objective
latency`` and stamps a ``__predicted__.serve`` block (max_batch,
per-device KV-cache bytes, forward_step_s) on the artifact — the handoff
serve/engine.py and verify/plan.py consume.  ``--serve --disagg N`` adds
per-phase blocks: the main search is the PREFILL plan, a companion
search on an N-device virtual slice under ``decode`` fills
``serve.decode`` (step time + inline op -> pc mapping), and
``serve.phase`` marks which phase the artifact's own plan is —
verify/plan.py charges the KV ring only to decode-phase plans.

``--decompose`` switches to the block-decomposed search (round 19):
the op graph is partitioned by the ``blk{i}_*`` layer-name prefixes,
identical transformer blocks share ONE fingerprint-keyed sub-search
(memoization), each unique block gets a warm-started masked MCMC over
its own ops at a proportional share of ``--iters``, and a global
boundary-refinement pass (``--boundary-refine-iters``, default 20% of
the budget) polishes the stitched plan.  ``--block-budget-s S``
additionally wall-caps each sub-search (0 = proposal-count bound only,
the bit-reproducible default).  Model names ``gpt-0.1b`` / ``gpt-0.4b``
/ ``gpt-1.3b`` / ``gpt-1.3b-deep`` build the models/gpt.py scale
presets (search-only shadow graphs; the preset owns batch/seq).  The
stdout line gains bench-shaped ``metric/value/unit/vs_baseline`` fields
plus the decomposition account (blocks, unique_blocks, memo_hits,
stitched_time_s) — the schema SEARCH_r01.json rows and
``make searchscale-smoke`` key on.

``-trace`` exports the simulated per-op timeline of the FINAL plan and
the pure-DP baseline as one Chrome/Perfetto ``trace_event`` JSON
(``<out-stem>.trace.json`` next to ``-o``, else
``<obs-dir>/<run-id>.trace.json``) — per-op/per-point compute intervals,
cross-device transfers with payload bytes, parameter-sync terms — and
emits a ``sim_trace`` obs record with the per-op simulated seconds that
``apps/report.py trace`` joins against measured ``op_time`` records for
drift attribution (see obs/trace.py).

Run telemetry (obs subsystem): ``-obs-dir DIR`` appends the structured
event stream (search_space, per-chunk MCMC trajectory, search_result,
per-op breakdown, pipeline + hlo_audit records) to
``DIR/<run-id>.jsonl``; ``-run-id ID`` names the run so several surfaces
share one stream.  With ``-o x.json`` and no ``-obs-dir``, the trace is
written next to the strategy as ``x.trace.jsonl``.  The saved JSON also
carries a ``__predicted__`` block (simulated dp/best step time) that a
consuming ``fit()`` turns into the ``sim_drift`` calibration gauge.
Render any of these with ``python -m flexflow_tpu.apps.report``.
"""

from __future__ import annotations

import json
import os
import sys

from flexflow_tpu.config import FFConfig
from flexflow_tpu.machine import MachineModel, Topology


def parse_args(argv):
    opts = {
        "model": "alexnet", "devices": None, "iters": 250_000,
        "out": "", "measured": False, "batch_size": 64, "seed": 0,
        "ici_group": None, "cache": "", "audit": None,
        "dtype": "float32", "dcn_calibration": "", "experts": 0,
        "obs_dir": "", "run_id": "", "chains": 1, "delta": "on",
        "trace": False, "objective": None, "serve": False,
        "disagg": 0, "decompose": False, "block_budget_s": 0.0,
        "boundary_refine_iters": 0,
    }
    from flexflow_tpu.utils.flags import flag_stream

    args = list(argv)
    if args and not args[0].startswith("-"):
        opts["model"] = args.pop(0)
    for a, val in flag_stream(args):
        if a == "--devices":
            opts["devices"] = int(val())
        elif a in ("-i", "--iters"):
            opts["iters"] = int(val())
        elif a in ("-o", "--out"):
            opts["out"] = val()
        elif a == "--measured":
            opts["measured"] = True
        elif a == "--cache":
            opts["cache"] = val()
        elif a in ("-b", "--batch-size"):
            opts["batch_size"] = int(val())
        elif a == "--seed":
            opts["seed"] = int(val())
        elif a == "--ici-group":
            opts["ici_group"] = int(val())
        elif a == "--audit":
            opts["audit"] = True
        elif a == "--no-audit":
            opts["audit"] = False
        elif a == "--dtype":
            # the searched plan's consuming driver may train bf16 — the
            # pipeline boundary-byte pricing follows this (VERDICT r4 #5)
            opts["dtype"] = val()
        elif a == "--dcn-calibration":
            # measured DCN-tier constants (utils/dcn_probe.py artifact)
            # replace the modeled Topology defaults (VERDICT r4 #6)
            opts["dcn_calibration"] = val()
        elif a == "--experts":
            # MoE transformer search (round 5: measured EP/TP costs)
            opts["experts"] = int(val())
        elif a in ("-obs-dir", "--obs-dir"):
            opts["obs_dir"] = val()
        elif a in ("-run-id", "--run-id"):
            opts["run_id"] = val()
        elif a in ("-chains", "--chains"):
            # parallel MCMC chains (native threads, deterministic
            # best-state exchange between chunks)
            opts["chains"] = int(val())
        elif a in ("-delta", "--delta"):
            # delta re-simulation: on (default) | off (full re-simulation
            # per proposal) | check (delta cross-checked vs full; debug)
            opts["delta"] = val()
        elif a in ("-trace", "--trace"):
            # export the simulated per-op timeline of the final plan AND
            # the pure-DP baseline as a Chrome/Perfetto trace
            # (ffsim_simulate_trace -> obs/trace.py)
            opts["trace"] = True
        elif a == "--objective":
            # makespan (default): price the full training step.
            # latency: price ONE forward/decode step from the same
            # simulator tables (serving SLO search — sim/search.py)
            opts["objective"] = val()
        elif a == "--serve":
            # emit a SERVING strategy artifact: implies --objective
            # latency unless one is given, and stamps a __predicted__
            # serve block (max_batch, per-device KV-cache bytes,
            # forward_step_s) that serve/engine.py reads for its virtual
            # clock and verify/plan.py for the forward-only HBM vet
            opts["serve"] = True
        elif a == "--disagg":
            # disaggregated serving artifact (serve/router.py): the main
            # search is the PREFILL phase's plan (latency objective);
            # a companion search on an N-device virtual decode slice
            # under the decode objective stamps serve.prefill /
            # serve.decode blocks with the per-phase step times
            opts["disagg"] = int(val())
        elif a == "--decompose":
            # block-decomposed search (round 19): per-layer sub-searches
            # with shared-block memoization + boundary refinement at the
            # same total proposal budget (sim/search.py
            # search_decomposed) — the path that converges on 1B+-param
            # graphs where flat MCMC stalls
            opts["decompose"] = True
        elif a == "--block-budget-s":
            # wall cap per block sub-search (0 = proposal-count bound
            # only, the bit-reproducible default)
            opts["block_budget_s"] = float(val())
        elif a == "--boundary-refine-iters":
            # proposals reserved for the post-stitch boundary refinement
            # pass (0 = the default 20% of --iters)
            opts["boundary_refine_iters"] = int(val())
    if opts["delta"] not in ("on", "off", "check"):
        raise SystemExit(f"-delta must be on|off|check, got "
                         f"{opts['delta']!r}")
    if opts["disagg"]:
        opts["serve"] = True
    if opts["objective"] is None:
        opts["objective"] = "latency" if opts["serve"] else "makespan"
    if opts["objective"] not in ("makespan", "latency", "decode"):
        raise SystemExit(f"--objective must be makespan|latency|decode, "
                         f"got {opts['objective']!r}")
    return opts


def build_model(name: str, machine: MachineModel, batch_size: int,
                dtype: str = "float32", experts: int = 0):
    if name == "nmt":
        from flexflow_tpu.nmt.rnn_model import RnnConfig, RnnModel

        return RnnModel(RnnConfig(batch_size=batch_size,
                                  compute_dtype=dtype), machine)
    if name in ("transformer", "gpt", "bert"):
        from flexflow_tpu.models.transformer import (TransformerConfig,
                                                     TransformerLM)

        return TransformerLM(TransformerConfig(batch_size=batch_size,
                                               compute_dtype=dtype,
                                               num_experts=experts),
                             machine)
    if name.startswith("gpt-"):
        # scale presets (models/gpt.py): gpt-0.1b / gpt-0.4b / gpt-1.3b /
        # gpt-1.3b-deep.  Presets own batch/seq (chosen so the DP
        # baseline shards legally and fits HBM at 1B+ params); the -b
        # flag is ignored here and main() re-reads the effective batch
        # off the built config.
        from flexflow_tpu.models.gpt import build_gpt

        return build_gpt(name[4:], machine, compute_dtype=dtype,
                         num_experts=experts)
    from flexflow_tpu.apps.cnn import _builders

    builders = _builders()
    if name not in builders:
        raise SystemExit(f"unknown model {name!r}")
    size = 299 if name.startswith("inception") else 224  # v3 is a 299 net
    cfg = FFConfig(batch_size=batch_size, input_height=size,
                   input_width=size, compute_dtype=dtype)
    return builders[name](cfg, machine)


def _audit_strategy(strategy, opts, machine, dp_known=None):
    """Save ``strategy`` to a temp JSON file and run the compiled-HLO
    collective audit against pure DP in a fresh virtual-mesh subprocess.
    ``dp_known`` from an earlier audit skips the duplicate DP lowering."""
    import os
    import tempfile

    from flexflow_tpu.utils.hlo_audit import audit_subprocess

    fd, path = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    try:
        strategy.save(path)
        return audit_subprocess(
            opts["model"], machine.num_devices,
            machine.topology.devices_per_ici_group, path,
            opts["batch_size"], timeout=1800.0, dtype=opts["dtype"],
            dp_known=dp_known, experts=opts.get("experts", 0),
            dcn_calibration=opts.get("dcn_calibration", ""))
    finally:
        os.unlink(path)


def _write_sim_trace(opts, search, info, olog, log):
    """The -trace export: full simulated timelines of the FINAL plan and
    the pure-DP baseline (two process lanes in one Perfetto-loadable
    file), plus a ``sim_trace`` obs record carrying the per-op simulated
    seconds — the join keys ``apps/report.py trace`` matches against
    measured ``op_time`` records for drift attribution."""
    from flexflow_tpu.obs import trace as obstrace

    best = search.simulate_trace(info["assignment"])
    dp = search.simulate_trace(search.dp_assignment())
    if opts["out"]:
        path = os.path.splitext(opts["out"])[0] + ".trace.json"
    elif opts["obs_dir"] and olog.enabled:
        path = os.path.join(opts["obs_dir"], f"{olog.run_id}.trace.json")
    else:
        path = f"{opts['model']}.trace.json"
    obstrace.write_trace(path, obstrace.chrome_trace(
        obstrace.sim_trace_events(best, pid=obstrace.PID_SIM_BEST,
                                  label="sim:best"),
        obstrace.sim_trace_events(dp, pid=obstrace.PID_SIM_DP,
                                  label="sim:dp")))
    olog.event("sim_trace", path=path, op_s=best["op_s"],
               total_s=best["total_s"], dp_total_s=dp["total_s"],
               opt_stream_s=best["opt_stream_s"])
    log(f"sim trace written to {path} (sim:best + sim:dp lanes; open in "
        f"ui.perfetto.dev)")
    return path


def _search_kw(opts):
    """search() keywords from the -chains / -delta flags."""
    return {"chains": opts.get("chains", 1),
            "delta": opts.get("delta", "on") != "off",
            "delta_check": opts.get("delta", "on") == "check"}


def _grounded_accept(opts, machine, model, cost_model, search, strategy,
                     info, log):
    """The executor-grounded accept path: audit the searched plan's
    compiled collectives in PREDICTED SECONDS (calibrated two-tier ring
    formulas — round 11; byte counts were the round-5 heuristic and
    remain the fallback); on contradiction fall back to a
    canonical-placement-only re-search, then to honest DP.  Returns
    (strategy, info, result_extras)."""
    from flexflow_tpu.sim.search import StrategySearch
    from flexflow_tpu.utils.hlo_audit import audit_consistent_time

    def summarize(audit, verdict):
        out = {
            "searched_cross_mb": round(
                audit["searched_cross_bytes"] / 1e6, 2),
            "dp_cross_mb": round(audit["dp_cross_bytes"] / 1e6, 2),
            "ratio": round(audit["cross_ratio_dp_over_searched"], 2),
            "consistent": verdict["consistent"],
            "mode": verdict["mode"],
        }
        if verdict.get("searched_pred_s") is not None:
            out["searched_pred_s"] = round(verdict["searched_pred_s"], 6)
        if verdict.get("dp_pred_s") is not None:
            out["dp_pred_s"] = round(verdict["dp_pred_s"], 6)
        return out

    def run_audit(s, speedup, dp_known=None, times=None):
        audit = _audit_strategy(s, opts, machine, dp_known=dp_known)
        verdict = audit_consistent_time(
            audit, speedup, topo=machine.topology,
            dp_time_s=times[0] if times else None,
            best_time_s=times[1] if times else None)
        if verdict["mode"] == "time":
            log(f"hlo audit: plan's compiled collectives predict "
                f"{verdict['searched_pred_s'] * 1e3:.2f} ms vs DP's "
                f"{verdict['dp_pred_s'] * 1e3:.2f} ms -> "
                f"{'CONSISTENT with' if verdict['consistent'] else 'CONTRADICTS'}"
                f" the simulated {speedup:.2f}x")
        else:
            log(f"hlo audit (byte fallback): plan moves "
                f"{audit['searched_cross_bytes'] / 1e6:.1f} MB cross-tier"
                f" vs DP's {audit['dp_cross_bytes'] / 1e6:.1f} MB -> "
                f"{'CONSISTENT with' if verdict['consistent'] else 'CONTRADICTS'}"
                f" the simulated {speedup:.2f}x")
        return audit, verdict

    try:
        audit, v = run_audit(strategy, info["speedup_vs_dp"],
                             times=(info["dp_time"], info["best_time"]))
    except Exception as e:  # audit rig unavailable: claim stays sim-only
        log(f"hlo audit unavailable ({e}); claim is simulation-only")
        return strategy, info, {"hlo_audit": {"error": str(e)}}
    if v["consistent"]:
        return strategy, info, {
            "hlo_audit": {**summarize(audit, v), "plan": "searched"}}
    rejected = summarize(audit, v)
    log("re-searching with canonical placements only (dims-only) — "
        "subset placement is what defeated the lowering")
    s2 = StrategySearch(model, machine, cost_model=cost_model,
                        placement=False, obs=search.obs,
                        objective=opts.get("objective", "makespan"))
    strategy2, info2 = s2.search(iters=opts["iters"], seed=opts["seed"],
                                 **_search_kw(opts))
    if info2["speedup_vs_dp"] > 1.05:
        try:
            audit2, v2 = run_audit(
                strategy2, info2["speedup_vs_dp"], dp_known=audit,
                times=(info2["dp_time"], info2["best_time"]))
        except Exception as e:
            log(f"hlo audit unavailable on re-search ({e})")
            audit2, v2 = None, {"consistent": False}
        if v2["consistent"]:
            return strategy2, info2, {"hlo_audit": {
                **summarize(audit2, v2), "plan": "canonical",
                "rejected_searched": rejected}}
        if audit2 is not None:
            rejected = {"rejected_searched": rejected,
                        "rejected_canonical": summarize(audit2, v2)}
        else:
            rejected = {"rejected_searched": rejected}
    else:
        log(f"canonical-only re-search finds no win "
            f"({info2['speedup_vs_dp']:.3f}x)")
        rejected = {"rejected_searched": rejected}
    log("executor audit rejects every >1x candidate; emitting honest DP")
    dp_strategy = search.assignment_to_strategy(search.dp_assignment())
    dp_info = {"dp_time": info["dp_time"], "best_time": info["dp_time"],
               "speedup_vs_dp": 1.0, "assignment": search.dp_assignment()}
    return dp_strategy, dp_info, {
        "hlo_audit": {**rejected, "plan": "dp", "consistent": True,
                      "note": "every simulated >1x plan contradicted by "
                              "the compiled program; DP emitted"}}


def _pipeline_grounded_accept(opts, machine, strategy, pp, log):
    """Grounded accept for an accepted ``__pipeline__`` block (round 11,
    VERDICT item 3: the 1.31x/1.72x pipeline wins carried no
    compiled-HLO audit).  Lower the SAME PipelinedLM the lm driver would
    run from the block, price its compiled collectives with the
    calibrated ring formulas, and require the result to stay within the
    modeled comm budget plus half the claimed win — a block whose
    compiled ppermutes/psums eat the win is vetoed.  Returns
    (ok, detail)."""
    import tempfile

    from flexflow_tpu.sim.collectives import priced_collectives
    from flexflow_tpu.strategy import Strategy
    from flexflow_tpu.utils.hlo_audit import audit_subprocess

    best = pp["best"]
    cand = next(c for c in pp["candidates"]
                if (c["stages"], c["microbatches"], c["tp"])
                == (best["stages"], best["microbatches"], best["tp"]))
    s = Strategy(strategy)
    s.pipeline = dict(best)
    fd, path = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    try:
        s.save(path)
        # dp_known=(0,0): the comparison here is compiled-vs-modeled comm
        # of the PIPELINED program; the DP lowering adds nothing
        audit = audit_subprocess(
            opts["model"], machine.num_devices,
            machine.topology.devices_per_ici_group, path,
            opts["batch_size"], timeout=1800.0, dtype=opts["dtype"],
            dp_known=(0.0, 0.0),
            dcn_calibration=opts.get("dcn_calibration", ""))
    finally:
        os.unlink(path)
    pred = priced_collectives(audit["searched_collectives"],
                              machine.topology)["seconds"]
    modeled = cand["comm_s"] + cand["tp_comm_s"] + cand["param_sync_s"]
    win = pp["reference_time_s"] - cand["time_s"]
    ok = pred <= modeled + 0.5 * win
    detail = {"plan": "pipeline", "consistent": ok,
              "compiled_pred_s": round(pred, 6),
              "modeled_comm_s": round(modeled, 6),
              "claimed_win_s": round(win, 6), **best}
    log(f"pipeline hlo audit: compiled program's collectives predict "
        f"{pred * 1e3:.2f} ms vs the {modeled * 1e3:.2f} ms modeled comm"
        f" (+ half the {win * 1e3:.2f} ms win) -> "
        f"{'CONSISTENT' if ok else 'CONTRADICTS the block'}")
    return ok, detail


def _decode_companion_search(opts, cost_model, olog, log) -> dict:
    """The ``--disagg N`` companion: search the DECODE phase's plan on
    its own N-device virtual slice under the ``decode`` objective
    (single-token forward + per-shard KV stream + sequence-shard
    collective pricing — sim/search.py).  Returns the serve.decode
    block: the searched step time plus the op -> pc mapping inline, so
    one artifact carries both phases' plans."""
    from flexflow_tpu.sim.search import StrategySearch

    n = opts["disagg"]
    machine = MachineModel.virtual(
        n, Topology(devices_per_ici_group=n))
    model = build_model(opts["model"], machine, opts["batch_size"],
                        opts["dtype"], opts["experts"])
    search = StrategySearch(model, machine, cost_model=cost_model,
                            obs=olog, objective="decode")
    strategy, info = search.search(iters=opts["iters"],
                                   seed=opts["seed"],
                                   **_search_kw(opts))
    log(f"disagg decode search: {n} device(s), step "
        f"{info['best_time']:.3e}s ({info['speedup_vs_dp']:.2f}x vs dp)")
    return {
        "devices": n,
        "objective": "decode",
        "step_time_s": info["best_time"],
        "speedup_vs_dp": info["speedup_vs_dp"],
        "strategies": {name: {"dims": list(pc.dims),
                              "devices": list(pc.devices)}
                       for name, pc in strategy.items()},
    }


def main(argv=None, log=print) -> dict:
    argv = list(sys.argv[1:] if argv is None else argv)
    opts = parse_args(argv)

    if opts["devices"]:
        ici = opts["ici_group"] or opts["devices"]
        if opts["dcn_calibration"]:
            topo = Topology.from_calibration(
                opts["dcn_calibration"], devices_per_ici_group=ici)
        else:
            topo = Topology(devices_per_ici_group=ici)
        machine = MachineModel.virtual(opts["devices"], topo)
    else:
        machine = MachineModel()
        if opts["ici_group"]:
            machine.topology = (
                Topology.from_calibration(
                    opts["dcn_calibration"],
                    devices_per_ici_group=opts["ici_group"])
                if opts["dcn_calibration"]
                else Topology(devices_per_ici_group=opts["ici_group"]))

    model = build_model(opts["model"], machine, opts["batch_size"],
                        opts["dtype"], opts["experts"])
    if opts["model"].startswith("gpt-"):
        # the preset owns batch/seq — downstream consumers (audit,
        # serve block, predicted stamp) must see the effective batch
        opts["batch_size"] = model.t.batch_size

    cost_model = None
    if opts["measured"]:
        from flexflow_tpu.sim.cost_model import MeasuredCostModel

        cost_model = MeasuredCostModel(cache_path=opts["cache"] or None)

    # run telemetry: an -obs-dir stream, or — when a strategy artifact is
    # being written — a search-trace JSONL next to it, so every committed
    # strategy has an auditable trajectory
    from flexflow_tpu import obs as _obs

    meta = {"app": "search", "model": opts["model"],
            "devices": machine.num_devices, "iters": opts["iters"],
            "measured": opts["measured"], "seed": opts["seed"],
            "chains": opts["chains"], "delta": opts["delta"],
            "objective": opts["objective"],
            "decompose": opts["decompose"]}
    if opts["obs_dir"]:
        run_id = opts["run_id"] or _obs.new_run_id()
        olog = _obs.RunLog(
            os.path.join(opts["obs_dir"], f"{run_id}.jsonl"),
            run_id=run_id, surface="search", meta=meta)
    elif opts["out"]:
        trace_path = os.path.splitext(opts["out"])[0] + ".trace.jsonl"
        olog = _obs.RunLog(trace_path, run_id=opts["run_id"] or None,
                           surface="search", meta=meta)
    else:
        olog = _obs.NULL

    from flexflow_tpu.sim.search import StrategySearch

    with _obs.span("ff:plan.search", proposals=opts["iters"],
                   chains=opts["chains"], ops=len(model.layers)):
        search = StrategySearch(model, machine, cost_model=cost_model,
                                obs=olog, objective=opts["objective"])
        if opts["decompose"]:
            strategy, info = search.search_decomposed(
                iters=opts["iters"], seed=opts["seed"],
                delta=opts.get("delta", "on") != "off",
                block_budget_s=opts["block_budget_s"] or None,
                boundary_refine_iters=opts["boundary_refine_iters"])
        else:
            strategy, info = search.search(iters=opts["iters"],
                                           seed=opts["seed"],
                                           **_search_kw(opts))
    result = {
        "model": opts["model"],
        "objective": opts["objective"],
        "devices": machine.num_devices,
        "dp_time_s": info["dp_time"],
        "best_time_s": info["best_time"],
        "speedup_vs_dp": info["speedup_vs_dp"],
    }
    if opts["decompose"]:
        # the bench-shaped fields every smoke/report surface keys on,
        # plus the decomposition account (how many sub-searches actually
        # ran vs were replayed from the shared-block memo)
        result.update({
            "metric": (f"{opts['model']}_decomposed_step_s_"
                       f"{machine.num_devices}dev"),
            "value": info["best_time"],
            "unit": "s",
            "vs_baseline": info["speedup_vs_dp"],
            "decomposed": True,
            "blocks": info["blocks"],
            "unique_blocks": info["unique_blocks"],
            "memo_hits": info["memo_hits"],
            "stitched_time_s": info["stitched_time"],
            "proposals_per_sec": info["proposals_per_sec"],
        })
    # ---- executor-grounded accept path (round 5, VERDICT r4 #1) ----
    # On a multi-tier machine, a simulated >1x win claims the plan moves
    # fewer bytes across the DCN tier than DP.  The compiled program is
    # the arbiter: lower plan + DP on a virtual mesh of the same shape
    # (a CPU-pinned subprocess — works from a parent that holds the chip),
    # count cross-tier collective bytes, and REJECT plans the lowering
    # contradicts (the round-4 transformer_2x4 falsification showed
    # GSPMD can lower 8x MORE cross-tier traffic than simulated).
    # Rejection cascade: full plan -> canonical-only (dims, no subset
    # placement) re-search -> honest DP.
    multi_tier = machine.topology.devices_per_ici_group \
        < machine.num_devices
    # default: audit exactly the runs that COMMIT a claim — a saved
    # artifact (-o) on a multi-tier machine claiming a win.  Ad-hoc
    # exploratory searches stay fast; --audit forces, --no-audit vetoes.
    do_audit = opts["audit"] if opts["audit"] is not None else (
        bool(opts["out"]) and multi_tier
        and info["speedup_vs_dp"] > 1.05)
    if do_audit:
        strategy, info, audit_info = _grounded_accept(
            opts, machine, model, cost_model, search, strategy, info, log)
        result.update(audit_info)
        result["best_time_s"] = info["best_time"]
        result["speedup_vs_dp"] = info["speedup_vs_dp"]
        # audit surface: same record schema as everything else
        olog.event("hlo_audit", **audit_info.get("hlo_audit", {}))
    if opts["model"] in ("transformer", "gpt", "bert") \
            and opts["objective"] == "makespan":
        # the GPipe scheduler configuration joins the search space for
        # the LM (round 4, VERDICT r3 #5): propose-or-reject a pipeline
        # block with every candidate's cost logged, feasibility-gated on
        # the executor's divisibility rules, accepted only when it beats
        # the best NON-pipelined plan (it replaces the per-op entries in
        # the consuming driver).  NMT is excluded: no NMT driver consumes
        # the block (PipelinedLM is a transformer stack).  The latency
        # objective is excluded too: GPipe schedules the TRAINING step
        # (fwd+bwd over microbatches); a serving strategy carries no
        # pipeline block.
        import math as _math

        pp = search.propose_pipeline(
            log=log, reference_s=info["best_time"],
            stage_divisor=model.t.num_layers,
            batch=model.t.batch_size,
            tp_divisor=_math.gcd(model.t.num_heads, model.t.d_ff))
        result["pipeline"] = {
            "accepted": pp["accepted"], "best": pp["best"],
            "reference_time_s": pp["reference_time_s"]}
        if pp["accepted"]:
            strategy.pipeline = pp["best"]
            # grounded accept for the block itself (round 11): an
            # accepted pipeline is a committed claim the same way a >1x
            # SOAP plan is — audit it whenever an artifact is written
            # (--audit forces, --no-audit vetoes)
            audit_pp = opts["audit"] if opts["audit"] is not None \
                else (bool(opts["out"]) and multi_tier)
            if audit_pp:
                try:
                    ok_pp, pp_detail = _pipeline_grounded_accept(
                        opts, machine, strategy, pp, log)
                except Exception as e:
                    log(f"pipeline hlo audit unavailable ({e}); block "
                        f"accepted simulation-only")
                    ok_pp, pp_detail = True, None
                if pp_detail is not None:
                    olog.event("hlo_audit", **pp_detail)
                    result["pipeline"]["audit"] = pp_detail
                if not ok_pp:
                    log("compiled program contradicts the pipeline win; "
                        "block dropped from the artifact")
                    strategy.pipeline = None
                    result["pipeline"]["accepted"] = False
    # the artifact carries its simulated prediction so a consuming fit()
    # can emit the sim_drift calibration gauge without re-searching
    strategy.predicted = {
        "model": opts["model"], "devices": machine.num_devices,
        "dp_time_s": info["dp_time"], "best_time_s": info["best_time"],
        "speedup_vs_dp": info["speedup_vs_dp"],
        "cost_model": "measured" if opts["measured"] else "analytic",
        "batch_size": opts["batch_size"],
        "objective": opts["objective"],
    }
    if opts["serve"]:
        # the serving block: serve/engine.py reads forward_step_s as its
        # virtual decode-step time, verify/plan.py charges the KV-cache
        # bytes against the forward-only per-device HBM peak
        from flexflow_tpu.serve.kv_cache import kv_cache_bytes

        strategy.predicted["serve"] = {
            "max_batch": opts["batch_size"],
            "kv_cache_bytes_per_device": kv_cache_bytes(
                model, opts["batch_size"], strategy=strategy),
            "forward_step_s": info["best_time"],
        }
        if opts["objective"] == "decode":
            # a decode-phase artifact: verify/plan.py charges the KV
            # ring to this pool (the prefill phase's vet passes 0)
            strategy.predicted["serve"]["phase"] = "decode"
        if opts["disagg"]:
            # per-phase blocks: the main search IS the prefill plan
            # (latency objective on the searched machine); the decode
            # phase gets its own searched step time on its own slice
            strategy.predicted["serve"]["phase"] = "prefill"
            strategy.predicted["serve"]["prefill"] = {
                "devices": machine.num_devices,
                "objective": opts["objective"],
                "step_time_s": info["best_time"],
            }
            strategy.predicted["serve"]["decode"] = \
                _decode_companion_search(opts, cost_model, olog, log)
        result["serve"] = strategy.predicted["serve"]
    if opts["trace"]:
        result["trace_path"] = _write_sim_trace(opts, search, info, olog,
                                                log)
    if olog.enabled:
        result["run_id"] = olog.run_id
        result["obs_path"] = olog.path
    log(json.dumps(result))
    if opts["out"]:
        if strategy.pipeline and not opts["out"].endswith(".json"):
            # the proto2 wire format is reference-byte-compatible and
            # cannot carry __pipeline__ — saving there would silently
            # drop the accepted block and the artifact would train
            # unpipelined (round-4 ADVICE): write a JSON sidecar that
            # carries the full plan
            sidecar = opts["out"] + ".pipeline.json"
            strategy.save(sidecar)
            log(f"warning: {opts['out']} is proto format, which cannot "
                f"carry the accepted __pipeline__ block — full plan "
                f"written to {sidecar}")
        strategy.save(opts["out"])
        log(f"strategy written to {opts['out']}")
    olog.spans()
    olog.close()
    return {"strategy": strategy, **result}


if __name__ == "__main__":
    main()
