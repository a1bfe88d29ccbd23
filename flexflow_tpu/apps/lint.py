"""Compile-time strategy verifier CLI (round 11) — ``make lint``.

    python -m flexflow_tpu.apps.lint alexnet --devices 8 --ici-group 4 \
        --strategy examples/strategies/alexnet_2x4.json

Runs the four verifier passes (flexflow_tpu/verify/):

1. **plan** (round 12) — the static strategy typechecker: per-op grid
   legality (divisibility, device range/duplicates, degradation,
   regrid reachability), pipeline-block consistency, and the
   dtype-aware per-device HBM-fit prediction — all BEFORE any build or
   compile, so a broken strategy file is a diagnostic list here
   instead of a mid-build traceback;
2. **sync** — source AST of the fit hot path, traced-jaxpr and
   compiled-HLO host-transfer scan of the jitted train step;
3. **donation** — input-output aliasing of the compiled executable
   (large non-donated update buffers) + a retrace count after two warm
   steps;
4. **predicted** — the grounded-accept audit in predicted seconds
   (searched strategy vs DP, calibrated two-tier ring formulas) against
   the strategy's own ``__predicted__`` claim.

``--json`` prints the findings machine-readably; ``--exemptions``
points at the approved-findings file (default
``flexflow_tpu/verify/exemptions.json``; every entry needs a reason).
Exit status 1 iff any non-exempt error-level finding survives.
``--source-only`` runs pass 1's AST leg alone (no jax, no mesh) — the
fast pre-commit form.
"""

from __future__ import annotations

import json
import os
import sys


def parse_args(argv):
    from flexflow_tpu.utils.flags import flag_stream

    opts = {"model": "alexnet", "devices": 8, "ici_group": None,
            "strategy": "", "batch_size": None, "seed": 3,
            "dtype": "float32", "json": False, "exemptions": None,
            "source_only": False, "skip_predicted": False,
            "overrides": None, "claimed_speedup": None,
            "dcn_calibration": "", "min_donation_mb": 1.0,
            "obs_dir": "", "run_id": "", "steps": 2,
            "allow_degraded": False}
    args = list(argv)
    if args and not args[0].startswith("-"):
        opts["model"] = args.pop(0)
    for a, val in flag_stream(args):
        if a == "--devices":
            opts["devices"] = int(val())
        elif a == "--ici-group":
            opts["ici_group"] = int(val())
        elif a == "--strategy":
            opts["strategy"] = val()
        elif a in ("-b", "--batch-size"):
            opts["batch_size"] = int(val())
        elif a == "--seed":
            opts["seed"] = int(val())
        elif a == "--dtype":
            opts["dtype"] = val()
        elif a == "--json":
            opts["json"] = True
        elif a == "--exemptions":
            opts["exemptions"] = val()
        elif a == "--source-only":
            opts["source_only"] = True
        elif a == "--skip-predicted":
            opts["skip_predicted"] = True
        elif a == "--overrides":
            opts["overrides"] = json.loads(val())
        elif a == "--claimed-speedup":
            opts["claimed_speedup"] = float(val())
        elif a == "--dcn-calibration":
            opts["dcn_calibration"] = val()
        elif a == "--min-donation-mb":
            opts["min_donation_mb"] = float(val())
        elif a == "--steps":
            # warm calls before the retrace count (0 skips execution;
            # at least 3 run so the cache can reach steady state)
            opts["steps"] = int(val())
        elif a in ("-obs-dir", "--obs-dir"):
            opts["obs_dir"] = val()
        elif a in ("-run-id", "--run-id"):
            opts["run_id"] = val()
        elif a == "--allow-degraded":
            opts["allow_degraded"] = True
    return opts


def _source_pass(repo):
    from flexflow_tpu.verify.sync_lint import source_sync_findings

    path = os.path.join(repo, "flexflow_tpu", "model.py")
    with open(path) as f:
        return source_sync_findings(f.read(), "flexflow_tpu/model.py")


def _plan_pass(opts, findings, summary) -> bool:
    """Static strategy typecheck + HBM-fit prediction (verify/plan.py)
    against a shadow model built WITHOUT the strategy.  Returns False
    when the plan has error findings — the build-dependent passes would
    crash mid-construction on such a strategy, so the caller skips
    them (their crash is exactly what this pass exists to replace)."""
    import jax

    from flexflow_tpu.machine import MachineModel, Topology
    from flexflow_tpu.utils.hlo_audit import _build_model
    from flexflow_tpu.verify.plan import (plan_findings,
                                          strategy_file_findings)

    ici = opts["ici_group"] or opts["devices"]
    machine = MachineModel(
        devices=jax.devices()[:opts["devices"]],
        topology=Topology(devices_per_ici_group=ici))
    fs, strategy = strategy_file_findings(opts["strategy"],
                                          where_prefix="")
    findings += fs
    if strategy is not None:
        shadow, _ = _build_model(
            opts["model"], machine, opts["batch_size"], "",
            opts["seed"], opts["dtype"], overrides=opts["overrides"])
        pfs, summary["plan"] = plan_findings(
            shadow, strategy, machine,
            allow_degraded=opts["allow_degraded"])
        findings += pfs
    return not any(f.pass_name == "plan" and f.severity == "error"
                   for f in findings)


def _step_passes(opts, findings, summary):
    """Build the model on the virtual mesh; jaxpr + HLO sync lint,
    donation/alias lint, retrace count."""
    import jax

    from flexflow_tpu.machine import MachineModel, Topology
    from flexflow_tpu.utils.hlo_audit import _build_model
    from flexflow_tpu.verify import donation_lint, sync_lint

    ici = opts["ici_group"] or opts["devices"]
    machine = MachineModel(
        devices=jax.devices()[:opts["devices"]],
        topology=Topology(devices_per_ici_group=ici))
    model, batch = _build_model(
        opts["model"], machine, opts["batch_size"], opts["strategy"],
        opts["seed"], opts["dtype"], overrides=opts["overrides"])
    if hasattr(model, "init_opt_state"):
        params, state = model.init()
        inputs = (params, state, model.init_opt_state(params)) + batch
    else:                       # PipelinedLM: params-only step
        inputs = (model.init(),) + batch
    step = model.make_train_step()
    traced = step.trace(*inputs)
    findings += sync_lint.jaxpr_sync_findings(traced.jaxpr)
    hlo = step.lower(*inputs).compile().as_text()
    findings += sync_lint.hlo_sync_findings(hlo)
    min_bytes = int(opts["min_donation_mb"] * 1e6)
    # enforcing since round 13: a large non-aliased ENTRY param is an
    # error here, with exemption ids covering the legitimate copies
    findings += donation_lint.donation_findings(hlo, min_bytes,
                                                enforce=True)
    summary["donation"] = donation_lint.donation_summary(hlo)
    if opts["steps"] > 0:
        # donation is a no-op on the CPU backend, so feeding outputs
        # back as inputs is safe here.  The first output-fed call may
        # legitimately trace once more (executor output shardings differ
        # from the init-time placements); steady state means the cache
        # stops growing on the LAST call — that growth is the genuine
        # per-step retrace signal
        out = step(*inputs)
        carry = len(inputs) - len(batch)
        sizes = [step._cache_size()]
        for _ in range(max(opts["steps"] - 1, 2)):
            out = step(*(tuple(out[:carry]) + batch))
            sizes.append(step._cache_size())
        findings += donation_lint.retrace_findings(
            step, max_traces=sizes[-2])
    return hlo


def _predicted_pass(opts, findings, summary):
    from flexflow_tpu.verify.predicted import predicted_findings

    ici = opts["ici_group"] or opts["devices"]
    fs, s = predicted_findings(
        opts["model"], opts["devices"], ici, opts["strategy"],
        opts["batch_size"], opts["seed"], opts["dtype"],
        opts["dcn_calibration"], opts["overrides"],
        opts["claimed_speedup"])
    findings += fs
    summary["predicted"] = s


def main(argv=None, log=print) -> int:
    from flexflow_tpu.verify.findings import (apply_exemptions, counts,
                                              load_exemptions)

    opts = parse_args(sys.argv[1:] if argv is None else argv)
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    findings, summary = [], {}
    ran_passes = {"sync"}
    findings += _source_pass(repo)
    if not opts["source_only"]:
        # the lint passes are defined on the virtual CPU mesh: pin the
        # platform BEFORE backend init (as hlo_audit.main does)
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count="
                f"{opts['devices']} " + os.environ.get("XLA_FLAGS", ""))
        import jax

        jax.config.update("jax_platforms", "cpu")
        plan_ok = True
        if opts["strategy"]:
            plan_ok = _plan_pass(opts, findings, summary)
            ran_passes.add("plan")
        if plan_ok:
            _step_passes(opts, findings, summary)
            ran_passes.add("donation")
            if opts["strategy"] and not opts["skip_predicted"]:
                _predicted_pass(opts, findings, summary)
                ran_passes.add("predicted")
        elif not opts["json"]:
            log("lint: plan errors — skipping the build-dependent "
                "passes (sync/donation/predicted need a constructible "
                "program)")
    exemptions = load_exemptions(
        opts["exemptions"]
        or os.path.join(repo, "flexflow_tpu", "verify", "exemptions.json"))
    findings, unused = apply_exemptions(findings, exemptions)
    for eid in unused:
        # only passes that RAN can prove an exemption stale: a
        # --source-only run must not flag the donation exemptions
        if eid.split(":", 1)[0] not in ran_passes:
            continue
        from flexflow_tpu.verify.findings import Finding

        findings.append(Finding(
            "exemptions", "unused", "error", eid,
            f"exemption {eid!r} matches no finding — prune it"))
    tally = counts(findings)
    record = {"model": opts["model"], "devices": opts["devices"],
              "strategy": opts["strategy"], **tally,
              "findings": [f.to_dict() for f in findings
                           if not f.exempted and f.severity != "info"],
              **summary}
    if opts["obs_dir"]:
        from flexflow_tpu import obs as _obs

        run_id = opts["run_id"] or _obs.new_run_id()
        olog = _obs.RunLog(os.path.join(opts["obs_dir"],
                                        f"{run_id}.jsonl"),
                           run_id=run_id, surface="lint",
                           meta={"app": "lint", "model": opts["model"]})
        olog.event("lint", **record)
        olog.close()
    if opts["json"]:
        log(json.dumps({**record,
                        "all_findings": [f.to_dict() for f in findings]}))
    else:
        for f in findings:
            if f.exempted:
                continue
            log(f"lint {f.severity} [{f.pass_name}:{f.code}] {f.message}")
        log(f"lint: {tally['error']} error(s), {tally['warning']} "
            f"warning(s), {tally['info']} info, {tally['exempted']} "
            f"exempted"
            + (f"; predicted pass: {summary['predicted']['mode']} "
               f"{'consistent' if summary['predicted']['consistent'] else 'INCONSISTENT'}"
               if "predicted" in summary else ""))
    return 1 if tally["error"] else 0


if __name__ == "__main__":
    sys.exit(main())
