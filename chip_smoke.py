"""Quickest proof that the system still starts on the chip.

    python chip_smoke.py

Drives the normal entry points once, in one process that holds the chip,
at the full width of models the repo supports (depth as published,
weights random from a seed), and checks what comes out by the repo's own
means:

  device      jax.devices() is a TPU
  train_cnn   apps.cnn.main -> FFModel.fit: Inception-v3 299x299, bf16,
              batch 256 per chip, 10 steps; finite losses, train state
              resident on every TPU device
  train_lm    apps.lm.main: causal LM b16 s512 l12 d768 h12 vocab 32k,
              bf16, 5 steps; finite losses AND the compiled step holds the
              flash-attention and fused projection+CE Mosaic custom calls
  serve       apps.serve.serve_run on the default gpt geometry: 8 requests,
              prompt 16, 8 new tokens, at --max-batch 8 and again at 1; all
              answered, replies equal

Any phase's failure fails the run.  Exit 0 and a last stdout line

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}

only when every phase passed on a TPU; on any other platform (or without
the package beside it) it exits non-zero and prints no result.  The line
before it carries per-phase compile seconds (apart from step seconds) and
persistent-cache hits, so a warm second run is visibly a cache hit; the
obs streams land under chiprun_out/chip_smoke/.

``--cpu-rehearsal`` is for debugging before chip time is spent: tiny
sizes on the CPU, ending in ``"ok": false`` and a non-zero exit — it
cannot be read as a pass.  It is an argument, never a default.
"""

import gc
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

# at the smoke's S=512 the backward is the one fused kernel (the split
# ff_flash_bwd_dkv + ff_flash_bwd_dq pair serves a fused backward whose
# VMEM count passes flash_attention._FUSED_BWD_BYTES)
FLASH_KERNELS = ("ff_flash_fwd", "ff_flash_bwd")
CE_KERNELS = ("ff_ce_fwd", "ff_ce_bwd")


class SmokeFailure(Exception):
    """A phase ran but what came out is wrong."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def compile_counters():
    """(backend compile seconds, cache hits, cache misses) so far, from
    the program's own ``compile.*`` counters (flexflow_tpu/obs/spans.py:
    JAX's monitoring events; a persistent-cache hit counts its
    retrieval under the backend seconds)."""
    from flexflow_tpu import obs

    c = obs.snapshot()["counters"]
    return (c.get("compile.backend_s", 0.0), c.get("compile.cache_hits", 0),
            c.get("compile.cache_misses", 0))


def _fit_checks(out, iters, what):
    """What every training phase must show: one finite loss per step and
    the train state resident on every device of the platform."""
    losses = out["loss"]
    check(len(losses) == iters, f"{what}: {len(losses)} losses for "
                                f"{iters} steps")
    check(all(math.isfinite(v) for v in losses),
          f"{what}: non-finite loss in {losses}")
    held = out["devices_held"]
    check(all(d["state_bytes"] > 0 for d in held),
          f"{what}: a device holds no train state: {held}")
    timed = out["completed_steps"] - 1  # fit's one warm-up step
    return {"steps": iters, "loss_first": losses[0], "loss_last": losses[-1],
            "step_s": out["elapsed_s"] / timed,
            "devices_held": held}


def _compiled_kernels(out):
    """The Pallas kernels in the phase's compiled train step, from the
    fit's obs ``compile`` record."""
    from flexflow_tpu.obs import read_events

    recs = [e for e in read_events(out["obs_path"])
            if e["kind"] == "compile"]
    check(recs, f"no compile record in {out['obs_path']}")
    return recs[-1]["pallas_kernels"]


def phase_train_cnn(rehearsal, device):
    from flexflow_tpu.apps import cnn

    iters = 3 if rehearsal else 10
    batch = 8 if rehearsal else 256 * device["count"]
    out = cnn.main(
        ["inception", "-b", str(batch), "--height", "299", "--width", "299",
         "--dtype", "bfloat16", "-i", str(iters), "-p", "0",
         "-obs-dir", OUT_DIR, "-run-id", "train_cnn"],
        log=lambda *a: None)
    info = _fit_checks(out, iters, "train_cnn")
    info["images_per_sec"] = out["images_per_sec"]
    info["pallas_kernels"] = _compiled_kernels(out)
    return info


def phase_train_lm(rehearsal, device):
    from flexflow_tpu.apps import lm

    iters = 3 if rehearsal else 5
    shape = (["-b", "8", "-s", "64", "-l", "2", "--d-model", "64",
              "--heads", "4", "--d-ff", "128", "--vocab", "512"]
             if rehearsal else
             ["-b", "16", "-s", "512", "-l", "12", "--d-model", "768",
              "--heads", "12", "--vocab", "32768"])
    out = lm.main(
        ["--causal", *shape, "--dtype", "bfloat16", "-i", str(iters),
         "-obs-dir", OUT_DIR, "-run-id", "train_lm"],
        log=lambda *a: None)
    info = _fit_checks(out, iters, "train_lm")
    info["tokens_per_sec"] = out["tokens_per_sec"]
    kernels = _compiled_kernels(out)
    info["pallas_kernels"] = kernels
    if not rehearsal:
        # routing is decided from the backend at trace time; only the
        # compiled program proves Mosaic built the kernels
        missing = [k for k in FLASH_KERNELS + CE_KERNELS
                   if k not in kernels]
        check(not missing, f"train_lm: compiled step holds no TPU custom "
                           f"call for {missing}; found {kernels}")
    return info


def phase_serve(rehearsal, device):
    from flexflow_tpu.apps import serve

    prompt, new = (4, 3) if rehearsal else (16, 8)

    def replies(max_batch):
        argv = ["gpt", "--requests", "8", "--prompt-len", str(prompt),
                "--max-new-tokens", str(new), "--max-batch", str(max_batch),
                "-obs-dir", OUT_DIR, "-run-id", f"serve_b{max_batch}"]
        if rehearsal:
            argv.append("--tiny")
        summary = serve.serve_run(serve.parse_args(argv),
                                  log=lambda *a: None)
        check(summary["completed"] == 8 and summary["unserved"] == 0,
              f"serve: {summary['completed']}/8 answered at --max-batch "
              f"{max_batch}")
        reqs = summary["_requests"]
        check(all(len(r.reply) == new for r in reqs),
              f"serve: reply lengths {[len(r.reply) for r in reqs]}")
        return ({r.rid: [int(t) for t in r.reply] for r in reqs},
                {"steps": summary["steps"], "wall_s": summary["wall_s"]})

    batched, info8 = replies(8)
    single, info1 = replies(1)
    check(batched == single,
          f"serve: replies at --max-batch 8 differ from the same requests "
          f"served at batch 1: {batched} vs {single}")
    return {"requests": 8, "batch8": info8, "batch1": info1,
            "replies_equal": True}


PHASES = (("train_cnn", phase_train_cnn), ("train_lm", phase_train_lm),
          ("serve", phase_serve))


def main(argv):
    from flexflow_tpu.utils.chip import (REHEARSAL_FLAG,
                                         enable_compile_cache, require_tpu)

    unknown = [a for a in argv if a != REHEARSAL_FLAG]
    if unknown:
        raise SystemExit(f"chip_smoke.py: unknown argument(s) {unknown}; "
                         f"the only one is {REHEARSAL_FLAG}")
    rehearsal = REHEARSAL_FLAG in argv

    import jax

    # phase `device`: anything but a TPU exits non-zero here, before any
    # result is printed (a rehearsal is pinned to the CPU)
    device = require_tpu("chip_smoke.py", rehearsal)
    print(f"chip_smoke: device platform={device['platform']} "
          f"kind={device['kind']!r} count={device['count']}"
          + ("  [CPU REHEARSAL — tiny sizes, not a chip pass]"
             if rehearsal else ""), flush=True)
    cache_dir = enable_compile_cache()
    shutil.rmtree(OUT_DIR, ignore_errors=True)

    phases = {}
    for name, fn in PHASES:
        t0, before = time.perf_counter(), compile_counters()
        info = fn(rehearsal, device)
        after = compile_counters()
        info.update(wall_s=time.perf_counter() - t0,
                    compile_s=after[0] - before[0],
                    cache_hits=after[1] - before[1],
                    cache_misses=after[2] - before[2])
        phases[name] = info
        print(f"chip_smoke: phase {name} ok — wall {info['wall_s']:.1f}s, "
              f"compile {info['compile_s']:.1f}s ({info['cache_hits']} "
              f"cache hit(s), {info['cache_misses']} miss(es))"
              + (f", step {info['step_s'] * 1e3:.1f} ms"
                 if "step_s" in info else ""), flush=True)
        # drop the phase's arrays and executables before the next one
        gc.collect()
        jax.clear_caches()

    report = {"phases": phases, "compile_cache_dir": cache_dir,
              "rehearsal": rehearsal}
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "report.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    if rehearsal:
        print(json.dumps({"ok": False, "rehearsal": True, "device": device}))
        print("chip_smoke: CPU rehearsal finished; this is not a chip "
              "pass (exit 3)", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
