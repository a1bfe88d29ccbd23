"""What the tolerances of ``benchmarks/configs/granite_4_0_h_micro.json``
rest on, read on the chip at published widths in one process: what the
comparison would read for a run in a coarser format than the
configuration states.  The float32 reference's loss and gradient of one
batch against the same reference with every product's operands rounded
first to ``bfloat16`` (what the program itself should read at least) and
to ``float8_e4m3fn`` (the nearest format below bfloat16, which has to
come out as not correct), by ``benchmarks/compare.py``'s own measures.
In the recurrence the rounded operands are ``delta x``, ``B``, ``C`` and
the state a step reads out.

    chiprun -- python3 tools/chip_granite_probe.py [--seed N]
        [--cpu-rehearsal]

Prints one JSON line and writes it to ``chiprun_out/granite_probe.json``.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import compare, harness
    from benchmarks.reference import granite_4_0_h_micro as ref
    from benchmarks.traffic_gen import fold_seed, load_mix
    from flexflow_tpu.utils.chip import enable_compile_cache, require_tpu

    device = require_tpu("tools/chip_granite_probe.py", args.cpu_rehearsal)
    if not args.cpu_rehearsal:
        enable_compile_cache()
    jax.config.update("jax_default_prng_impl", "rbg")
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite_4_0_h_micro.json")) as f:
        config = json.load(f)
    mix = load_mix("train_1chip_b2_s8192_ref2")
    if args.cpu_rehearsal:
        config.update(config["rehearsal"])
        mix.update(mix["rehearsal"])
    built = harness.load_by_name(
        os.path.join(ROOT, "benchmarks", "builders"),
        config["builder"]).build_train(config, mix, jax.devices()[:1],
                                       args.seed)
    ff = built["model"]
    params, _ = jax.jit(ff.init)(np.int32(fold_seed(args.seed, 0)))
    batch = built["make_batch"](np.int32(fold_seed(args.seed, 2)))
    plain = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                         built["op_params"](params))
    del params

    def grads(operands):
        ref.OPERANDS = operands
        t0 = time.perf_counter()
        try:
            with jax.default_matmul_precision("highest"):
                loss, g, n = jax.jit(lambda p, b: ref.sum_loss_and_grads(
                    p, b, config))(plain, batch)
            n = int(n)
            return float(loss) / n, jax.tree.map(
                lambda a: np.asarray(a, np.float32) / n, g), \
                time.perf_counter() - t0
        finally:
            ref.OPERANDS = None

    out = {"device": device, "seed": args.seed}
    loss32, g32, out["float32_seconds"] = grads(None)
    no_floor = jax.tree.map(np.zeros_like, g32)
    for name, operands in (("bfloat16_products", jnp.bfloat16),
                           ("float8_e4m3fn_products", jnp.float8_e4m3fn)):
        loss, g, seconds = grads(operands)
        per = compare.per_op_errors(g, g32, no_floor)
        by_err = sorted(per, key=lambda k: -per[k]["raw"])
        out[name] = {"loss_rel": abs(loss - loss32) / abs(loss32),
                     "grad_rel_l2": compare.rel_l2(g, g32),
                     "worst_ops": [[k, per[k]["raw"]] for k in by_err[:4]],
                     "worst_op_grad_rel_l2": per[by_err[0]]["raw"],
                     "median_op_grad_rel_l2": float(np.median(
                         [v["raw"] for v in per.values()])),
                     "seconds": seconds}
        del g
    line = json.dumps(out)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "granite_probe.json"),
              "w") as f:
        f.write(line + "\n")
    print(line, flush=True)
    return 3 if args.cpu_rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
