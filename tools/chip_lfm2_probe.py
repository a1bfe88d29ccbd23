"""What the tolerances and the assumed embedding spread of an expert
configuration (``benchmarks/configs/lfm2_8b_a1b.json`` by default,
another by ``--config`` and ``--mix``: ``smallthinker_21b_a3b`` and
``train_1chip_b1_s16384``) rest on, read on the chip at published widths
in one process:

1. for each candidate spread of the embedding (``--embedding-stds``; the
   head is tied, so the spread is the logits' too): the starting loss, the
   routers' load (the fullest expert's over the mean, by expert layer) and
   the (token, held expert) pairs against the buffer's ``rows_capacity``
   (pairs beyond it would be dropped, and must read 0), from the program's
   own forward pass; then ``--steps`` training steps of the program from
   those weights: the loss of each, and the load and the dropped pairs of
   the last (the table is one draw scaled, which is what a spread is; an
   untied head keeps its own matrix);
2. at the configuration's own spread, the share of (token, expert layer)
   top-k selections on which the program (bfloat16 activations, a float32
   router) and the float32 reference disagree;
3. what the comparison would read for a run in a coarser format than the
   configuration states: the reference's loss and gradient with every
   product's operands rounded to ``float8_e4m3fn`` (the nearest format
   below bfloat16), with the router's alone in bfloat16, and with the
   operands in bfloat16 (what the program itself should read at least),
   each against the float32 reference, by ``benchmarks/compare.py``'s own
   measures.

    chiprun -- python3 tools/chip_lfm2_probe.py [--seed N]
        [--embedding-stds 0.05,0.3,1.0] [--steps 6] [--at-std 0.3]
        [--selections-only] [--cpu-rehearsal]
        [--config lfm2_8b_a1b --mix train_1chip_b2_s8192_ref2]

Prints one JSON line and writes it to ``chiprun_out/<config>_probe.json``.
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
    ap.add_argument("--embedding-stds", default="",
                    help="comma-separated spreads to read loss and load at")
    ap.add_argument("--at-std", type=float, default=None,
                    help="the spread parts 2 and 3 are read at (the "
                         "configuration's own where not given)")
    ap.add_argument("--steps", type=int, default=6,
                    help="training steps from each candidate spread")
    ap.add_argument("--selections-only", action="store_true",
                    help="parts 1 and 2: no coarser-format readings")
    ap.add_argument("--config", default="lfm2_8b_a1b")
    ap.add_argument("--mix", default="train_1chip_b2_s8192_ref2")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import compare, harness
    from benchmarks.traffic_gen import fold_seed, load_mix
    from flexflow_tpu.utils.chip import enable_compile_cache, require_tpu

    t_start = time.perf_counter()

    def say(what):      # progress, so that a slow stage shows which it is
        print(f"probe: {time.perf_counter() - t_start:7.1f} s  {what}",
              file=sys.stderr, flush=True)

    device = require_tpu("tools/chip_lfm2_probe.py", args.cpu_rehearsal)
    if not args.cpu_rehearsal:
        enable_compile_cache()
    jax.config.update("jax_default_prng_impl", "rbg")
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    mix = load_mix(args.mix)
    ref = harness.load_by_name(os.path.join(ROOT, "benchmarks",
                                            "reference"), args.config)
    if args.cpu_rehearsal:
        config.update(config["rehearsal"])
        mix.update(mix["rehearsal"])
    builder = harness.load_by_name(
        os.path.join(ROOT, "benchmarks", "builders"), config["builder"])

    if args.at_std is not None:
        config["embedding_std"] = args.at_std
    own_std = float(config["embedding_std"])
    built = builder.build_train(config, mix, jax.devices()[:1], args.seed)
    ff = built["model"]
    tokens, _ = built["make_batch"](np.int32(fold_seed(args.seed, 2)))
    routers = [op for op in ff.layers if op.name.endswith("_moe_router")]
    experts = [op for op in ff.layers if op.name.endswith("_moe_experts")]
    lo, hi = experts[0].experts_held
    capacity = experts[0].rows_capacity

    @jax.jit
    def fresh(seed32, std):
        """The program's own initial state with the table at ``std``."""
        params, state = ff.init(seed32)
        table = params["embed"]["table"] * (std / own_std)
        return dict(params, embed={"table": table}), state

    @jax.jit
    def forward(params, state, tokens):
        values, _ = ff.apply(params, state, {ff.tokens.tid: tokens,
                                             ff.labels.tid: tokens}, False)
        loss, _ = ff.loss_fn(params, state, tokens, tokens, False)
        return loss, jnp.stack([values[op.output.tid] > 0
                                for op in routers])

    def load_of(picked):
        pairs = picked[..., lo:hi].sum(axis=(1, 2, 3))
        load = picked.sum(axis=(1, 2)).astype(np.float64)
        return {"held_pairs_by_layer": [int(x) for x in pairs],
                "pairs_beyond_capacity": int(np.maximum(
                    pairs - capacity, 0).sum()),
                "load_max_over_mean": [float(x) for x in
                                       load.max(axis=1) / load.mean(axis=1)]}

    def read(std):
        params, state = fresh(np.int32(fold_seed(args.seed, 0)),
                              np.float32(std))
        loss, picked = forward(params, state, tokens)
        return params, state, float(loss), np.asarray(picked)

    out = {"device": device, "seed": args.seed, "rows_capacity": capacity,
           "by_embedding_std": {}}
    step = ff.make_train_step() if args.steps else None
    for std in (float(x) for x in args.embedding_stds.split(",") if x):
        params, state, loss, picked = read(std)
        here = dict(starting_loss=loss, **load_of(picked))
        say(f"embedding {std}: starting loss {loss:.4f}")
        if step is not None:
            opt, losses = ff.init_opt_state(params), []
            for _ in range(args.steps):
                params, state, opt, loss = step(params, state, opt, tokens,
                                                tokens)
                losses.append(float(loss))
                say(f"embedding {std}: step loss {losses[-1]:.4f}")
            counts = np.stack([np.asarray(state[op.name]["counts"])
                               for op in experts])
            here.update(
                step_losses=losses,
                last_step_dropped=float(sum(np.asarray(
                    state[op.name]["dropped"]) for op in experts)),
                last_step_load_max_over_mean=[
                    float(x) for x in counts.max(axis=1)
                    / counts.mean(axis=1)])
            del opt
        out["by_embedding_std"][str(std)] = here
        del params, state
    del step

    params, state, loss, ours = read(own_std)
    out.update(embedding_std=own_std, starting_loss=loss, **load_of(ours))
    say("the program's selections read")
    # on the host: each reference gradient below takes its own device copy
    # and gives it up to the gradient (donated), so that a reference that
    # fits beside one float32 tree of the weights fits here
    plain = jax.tree.map(lambda a: np.asarray(a, np.float32),
                         built["op_params"](params))
    del params
    with jax.default_matmul_precision("highest"):
        theirs = np.concatenate([np.asarray(jax.jit(
            lambda p, t: ref.router_selections(p, t, config))(
                plain, tokens[i:i + 1])) for i in range(tokens.shape[0])],
            axis=1)
    say("the reference's selections read")
    differ = np.any(ours != theirs, axis=-1)
    out.update(selections=int(differ.size),
               selections_that_differ=int(differ.sum()),
               share_that_differs=float(differ.mean()),
               share_by_layer=[float(x) for x in differ.mean(axis=(1, 2))],
               # of the experts a token takes, how many are others
               experts_that_differ=float(
                   (ours & ~theirs).sum() / max(ours.sum(), 1)))

    batch = (tokens[:1], tokens[:1])        # one sequence: half the time

    def grads(operands, router):
        ref.OPERANDS, ref.ROUTER_OPERANDS = operands, router
        t0 = time.perf_counter()
        try:
            with jax.default_matmul_precision("highest"):
                loss, g, n = jax.jit(lambda p, b: ref.sum_loss_and_grads(
                    p, b, config), donate_argnums=0)(
                        jax.device_put(plain), batch)
            n = int(n)
            say(f"reference gradient, operands {operands}, router {router}")
            return float(loss) / n, jax.tree.map(
                lambda a: np.asarray(a, np.float32) / n, g), \
                time.perf_counter() - t0
        finally:
            ref.OPERANDS = ref.ROUTER_OPERANDS = None

    formats = () if args.selections_only else (
        ("bfloat16_products", jnp.bfloat16, jnp.float32),
        ("float8_e4m3fn_products", jnp.float8_e4m3fn, jnp.float32),
        ("bfloat16_router", None, jnp.bfloat16))
    if formats:
        loss32, g32, out["float32_seconds"] = grads(None, None)
        no_floor = jax.tree.map(np.zeros_like, g32)
    for name, operands, router in formats:
        loss, g, seconds = grads(operands, router)
        per = compare.per_op_errors(g, g32, no_floor)
        by_err = sorted(per, key=lambda k: -per[k]["raw"])
        out[name] = {"loss_rel": abs(loss - loss32) / abs(loss32),
                     "grad_rel_l2": compare.rel_l2(g, g32),
                     "worst_ops": [[k, per[k]["raw"]] for k in by_err[:4]],
                     "worst_op_grad_rel_l2": per[by_err[0]]["raw"],
                     "median_op_grad_rel_l2": float(np.median(
                         [v["raw"] for v in per.values()])),
                     "seconds": seconds}
        say(f"{name}: {json.dumps(out[name])}")
        del g
    line = json.dumps(out)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"{args.config}_probe.json"), "w") as f:
        f.write(line + "\n")
    print(line, flush=True)
    return 3 if args.cpu_rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
