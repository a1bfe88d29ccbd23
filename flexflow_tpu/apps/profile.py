"""Deep-profile a model's compiled train step on the local chip:

    python -m flexflow_tpu.apps.profile inception -b 256 \
        -o examples/profiles/inception_v3_roofline.json

Runs the real jitted step, records a device trace, attributes device time
per HLO op (classified MXU vs VPU vs unfusable against the compiled HLO),
and emits the roofline ceiling analysis (utils/hlo_profile.py).  This is
the evidence artifact for perf claims: the reference's only instrument is
the per-task cudaEvent print (conv_2d.cu:514-545)."""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time


def profile_model(model: str = "inception", batch_size: int = 256,
                  iters: int = 10, dtype: str = "bfloat16",
                  top_n: int = 25) -> dict:
    import jax

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.data import synthetic_batches
    from flexflow_tpu.machine import MachineModel
    from flexflow_tpu.sim.cost_model import chip_perf
    from flexflow_tpu.utils.hlo_profile import (classify_ops,
                                                device_op_times,
                                                roofline_report)

    if model == "inception":
        from flexflow_tpu.models.inception import build_inception_v3 as build
        size = 299
    elif model == "alexnet":
        from flexflow_tpu.models.alexnet import build_alexnet as build
        size = 224
    else:
        raise SystemExit(f"unknown model {model!r}")

    machine = MachineModel()
    # the roofline is a fraction of THIS device's peaks: an unknown
    # device_kind raises here, before any step is timed
    perf = chip_perf(machine.devices[0].device_kind)
    cfg = FFConfig(batch_size=batch_size, input_height=size,
                   input_width=size, num_iterations=iters, print_freq=0,
                   compute_dtype=dtype)
    ff = build(cfg, machine)
    params, state = ff.init()
    opt_state = ff.init_opt_state(params)
    step = ff.make_train_step()
    data = synthetic_batches(machine, batch_size, size, size, mode="ones")
    img, lbl = next(data)
    for _ in range(3):
        params, state, opt_state, loss = step(params, state, opt_state,
                                              img, lbl)
    jax.block_until_ready(loss)  # sync-ok: warm-up fence
    t0 = time.perf_counter()
    for _ in range(iters):
        params, state, opt_state, loss = step(params, state, opt_state,
                                              img, lbl)
    jax.block_until_ready(loss)  # sync-ok: closes the timed window
    sec = (time.perf_counter() - t0) / iters

    trace_steps = 2
    logdir = tempfile.mkdtemp(prefix="ffprof_")
    with jax.profiler.trace(logdir):
        for _ in range(trace_steps):
            params, state, opt_state, loss = step(params, state, opt_state,
                                                  img, lbl)
        jax.block_until_ready(loss)  # sync-ok: inside the traced window

    compiled = step.lower(params, state, opt_state, img, lbl).compile()
    times = device_op_times(logdir, steps=trace_steps)
    rows, totals = classify_ops(compiled.as_text(), times)
    report = roofline_report(compiled, sec, perf, totals,
                             n_devices=machine.num_devices)
    report["device_kind"] = machine.devices[0].device_kind
    report["devices"] = machine.num_devices
    report["model"] = model
    report["batch_size"] = batch_size
    report["dtype"] = dtype
    report["images_per_sec"] = batch_size / sec
    report["top_ops"] = [
        {"ms": round(ms, 3), "class": c, "name": n, "root": r[:160]}
        for ms, c, n, r in rows[:top_n]
    ]
    return report


def main(argv=None, log=print):
    argv = list(sys.argv[1:] if argv is None else argv)
    model, batch, out = "inception", 256, ""
    from flexflow_tpu.utils.flags import flag_stream

    if argv and not argv[0].startswith("-"):
        model = argv.pop(0)
    for a, val in flag_stream(argv):
        if a in ("-b", "--batch-size"):
            batch = int(val())
        elif a in ("-o", "--out"):
            out = val()
    report = profile_model(model, batch)
    log(json.dumps({k: v for k, v in report.items() if k != "top_ops"},
                   indent=1, default=str))
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump(report, f, indent=1, default=str)
        log(f"report written to {out}")
    return report


if __name__ == "__main__":
    from flexflow_tpu.utils.chip import enable_compile_cache

    enable_compile_cache()  # before the first compile
    main()
