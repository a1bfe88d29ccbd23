"""Four chips through the normal drivers: DP at scale, and a searched
hybrid plan against DP.

    python tools/chip_multi.py        # on a host with >= 2 chips
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python tools/chip_multi.py --cpu-rehearsal   # tiny, no chip

One process holds the chips and runs, in turn (issue 21 section 5):

  inception_dp   apps.cnn.main: Inception-v3 pure DP, 299x299 bf16, global
                 batch 256 per chip, 30 steps (a window of 7 timed
                 steps read 283 ms a step on four chips, PR 21)
  search         apps.search.main alexnet --devices <n> -o <plan>: the
                 offline search for THIS device count (the committed
                 strategies are for 8 and 16 devices)
  alexnet_dp     apps.cnn.main alexnet, float32, seed 0, 6 steps
  alexnet_plan   the same run under ``-s <plan>``: the plan checker must
                 pass, the plan must hold a non-DP grid or a placement
                 group, and the losses must follow the DP run's (the
                 repo's core invariant — a plan changes where the work
                 runs, never what is computed)
  alexnet_dp_half  the same run with every op data-parallel over half
                 the chips: the same mathematics under other shard sizes
                 and another reduction tree.  How far it leaves
                 alexnet_dp is what rounding alone does to this
                 trajectory — the noise floor the plan's departure is
                 read against

and for every run prints what each chip holds (train-state bytes from
``addressable_shards`` and the runtime's ``memory_stats``), so a plan
that leaves a chip empty shows.  Exit 1 when a check fails; the last
stdout line is one JSON report, also written to chiprun_out/multi/.
"""

import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT_DIR = os.path.join(ROOT, "chiprun_out", "multi")

# float32 with "highest" matmul precision: the hybrid plan splits
# contractions the DP run keeps whole, and the TPU's default single bf16
# pass would turn that reassociation into 1e-3-level loss noise.  The
# first loss (same parameters, other partitioning) must agree to float32
# rounding.  After it each step feeds the rounding difference back
# through the update, so the bound for step i is the invariant's 1e-4 or,
# where rounding alone is measured to exceed that, FLOOR_FACTOR times the
# largest departure of alexnet_dp_half from alexnet_dp up to step i.  (On
# four v5e chips, PR 21: the plan leaves DP by at most 1.1e-4 over six
# steps, DP on half the chips by 1.9e-4, the DP run repeated by nothing.)
FIRST_LOSS_RTOL = 1e-6
LOSS_RTOL = 1e-4
FLOOR_FACTOR = 4.0


def _check(cond, msg):
    if not cond:
        raise SystemExit(f"chip_multi: FAILED — {msg}")


def _run_cnn(argv, what, rehearsal, every_chip=True):
    from flexflow_tpu.apps import cnn

    out = cnn.main(argv, log=lambda *a: None)
    losses = out["loss"]
    _check(losses and all(math.isfinite(v) for v in losses),
           f"{what}: losses {losses}")
    held = out["devices_held"]
    _check(not every_chip or all(
        d["state_bytes"] > 0 and (rehearsal or d["peak_bytes_in_use"])
        for d in held), f"{what}: a chip holds nothing: {held}")
    timed = out["completed_steps"] - 1
    rec = {"loss": losses, "step_s": out["elapsed_s"] / timed,
           "images_per_sec": out["images_per_sec"], "devices_held": held}
    print(f"chip_multi: {what}: step {rec['step_s'] * 1e3:.1f} ms, "
          f"per-chip state MB "
          f"{[round(d['state_bytes'] / 1e6, 1) for d in held]}, in use MB "
          f"{[round((d['bytes_in_use'] or 0) / 1e6, 1) for d in held]}, "
          f"process-lifetime peak MB "
          f"{[round((d['peak_bytes_in_use'] or 0) / 1e6, 1) for d in held]}",
          flush=True)
    return rec


def inception_dp(report, n, rehearsal):
    per_chip = 2 if rehearsal else 256
    report["inception_dp"] = _run_cnn(
        ["inception", "-b", str(per_chip * n), "--height", "299",
         "--width", "299", "--dtype", "bfloat16",
         "-i", "3" if rehearsal else "30", "-p", "0"],
        "inception_dp", rehearsal)


def alexnet_plan_vs_dp(report, n, rehearsal):
    """Fills ``report``; returns what is wrong with the plan's loss
    trajectory, or None."""
    import jax

    from flexflow_tpu.apps import search
    from flexflow_tpu.strategy import ParallelConfig, Strategy

    jax.config.update("jax_default_matmul_precision", "highest")
    plan = os.path.join(OUT_DIR, f"alexnet_{n}dev.json")
    batch = str((8 if rehearsal else 64) * n)
    res = search.main(["alexnet", "--devices", str(n), "-b", batch,
                       "-o", plan], log=lambda *a: None)
    strategy = Strategy.load(plan)
    dp_dims = {4: (1, 1, 1, n), 2: (1, n), 1: (n,)}
    hybrid = sorted(name for name, pc in strategy.items()
                    if tuple(pc.dims) != dp_dims.get(len(pc.dims))
                    or len(pc.devices) != n)
    _check(hybrid, f"the searched plan is pure DP: {dict(strategy)}")
    report["search"] = {
        "plan": os.path.relpath(plan, ROOT), "non_dp_ops": hybrid,
        "predicted_speedup_vs_dp": res["speedup_vs_dp"],
        "predicted_dp_s": res["dp_time_s"],
        "predicted_best_s": res["best_time_s"]}
    print(f"chip_multi: search: {len(hybrid)} non-DP op(s) {hybrid}, "
          f"simulator predicts {res['speedup_vs_dp']:.2f}x over DP",
          flush=True)

    # lr 0.001: at the default 0.01 AlexNet on Gaussian inputs diverges
    # within six steps, and a diverging trajectory compares nothing
    common = ["alexnet", "-b", batch, "--dtype", "float32", "-i", "6",
              "-p", "0", "--seed", "0", "--lr", "0.001"]
    report["alexnet_dp"] = _run_cnn(common, "alexnet_dp", rehearsal)
    # cnn.main runs the static plan checker on -s and exits 2 on errors
    report["alexnet_plan"] = _run_cnn(common + ["-s", plan], "alexnet_plan",
                                      rehearsal)
    half_plan = os.path.join(OUT_DIR, f"alexnet_dp{n // 2}of{n}.json")
    # (the loss op stays on the whole machine: the executor cannot place
    # it on a subset of the devices, plan check degraded_normalized)
    Strategy({name: ParallelConfig.data_parallel(pc.ndims, n // 2)
              for name, pc in strategy.items()
              if pc.ndims > 1}).save(half_plan)
    report["alexnet_dp_half"] = _run_cnn(
        common + ["-s", half_plan], "alexnet_dp_half", rehearsal,
        every_chip=False)

    def departure(what):
        a, b = report["alexnet_dp"]["loss"], report[what]["loss"]
        _check(len(a) == len(b), f"{what}: {len(b)} losses for {len(a)}")
        return [abs(x - y) / max(abs(x), 1e-12) for x, y in zip(a, b)]

    diffs, floor = departure("alexnet_plan"), departure("alexnet_dp_half")
    bound = [FIRST_LOSS_RTOL] + [
        max(LOSS_RTOL, FLOOR_FACTOR * max(floor[:i + 1]))
        for i in range(1, len(floor))]
    report.update(loss_rel_diff=diffs, loss_rel_diff_floor=floor,
                  loss_rel_bound=bound)
    print(f"chip_multi: plan vs DP {[f'{d:.1e}' for d in diffs]}, "
          f"DP on half the chips vs DP {[f'{d:.1e}' for d in floor]}",
          flush=True)
    if all(d <= b for d, b in zip(diffs, bound)):
        return None
    return (f"hybrid losses {report['alexnet_plan']['loss']} leave the DP "
            f"run's {report['alexnet_dp']['loss']}: relative differences "
            f"{diffs} against bounds {bound} (the same DP on half the "
            f"chips differs by {floor})")


def main(argv):
    import jax

    from flexflow_tpu.utils.chip import (REHEARSAL_FLAG,
                                         enable_compile_cache, require_tpu)

    rehearsal = REHEARSAL_FLAG in argv
    device = require_tpu("chip_multi.py", rehearsal)
    n = device["count"]
    _check(n >= 2, f"needs several chips, found {n}")
    enable_compile_cache()
    os.makedirs(OUT_DIR, exist_ok=True)
    report = {"device": device, "rehearsal": rehearsal}
    inception_dp(report, n, rehearsal)
    jax.clear_caches()
    wrong = alexnet_plan_vs_dp(report, n, rehearsal)
    # the report is the record of a run that is dear to repeat: write it
    # before the verdict
    with open(os.path.join(OUT_DIR, "report.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    _check(wrong is None, wrong)
    return 3 if rehearsal else 0  # a rehearsal is not a chip pass


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
