"""Driver for mixes of ``"kind": "train"``: the normal training path
(the model's donated train step fed through ``DevicePrefetcher``, as
``bench.py`` and ``fit`` drive it) for ``--seconds`` seconds.

Set-up, in the order the phases are printed: the plan (read from the
checkout's ``.bench_cache``, searched only when it is not there), the
model, its weights and optimizer state made on the device from the seed
in one jitted call, one fixed batch made on the device from the seed,
the step's compilation and ``warmup_steps`` steps.  The window counts
whole steps between ``block_until_ready`` fences every ``fence_every``
steps.  The comparison with the reference runs after the window.
"""

from __future__ import annotations

import itertools
import math
import os
import time
from typing import Dict


def _plan_file(ctx) -> str:
    """The strategy file of a mix that asks for one: kept beside the
    compile cache, so only a checkout's first run searches."""
    plan = ctx.mix.get("plan")
    if not plan:
        return ""
    path = os.path.join(ctx.keep, "plans",
                        f"{ctx.config['name']}.{ctx.mix['name']}"
                        + ("" if not ctx.rehearsal else ".rehearsal")
                        + ".json")
    if not os.path.isfile(path):
        from flexflow_tpu.apps import search

        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp.json"
        args = [str(a).replace("{batch}", str(ctx.mix["batch"]))
                .replace("{chips}", str(ctx.mix["chips"]))
                for a in plan["search_args"]]
        search.main(args + ["-o", tmp], log=lambda *a: None)
        os.replace(tmp, path)
    return path


def run(ctx) -> Dict:
    import jax
    import numpy as np

    from benchmarks import compare
    from benchmarks.traffic_gen import fold_seed
    from flexflow_tpu.data.prefetch import DevicePrefetcher

    spans = ctx.spans
    plan_file = _plan_file(ctx)
    ctx.phases.mark("plan")

    built = ctx.builder().build_train(ctx.config, ctx.mix, ctx.devices,
                                      ctx.seed, plan_file)
    ff = built["model"]

    def _fresh_state(seed):
        params, state = ff.init(seed)
        return params, state, ff.init_opt_state(params)

    # one jitted call makes the whole train state on the device, laid out
    # as init() would lay it out leaf by leaf (else the step would
    # compile a second time for its own outputs' shardings).  The seed is
    # an argument, not a constant of the program: every seed finds the
    # same program in the compile cache.
    make_state = jax.jit(_fresh_state, out_shardings=jax.tree.map(
        lambda a: getattr(a, "sharding", None), ff.abstract_train_state()))
    seed32 = np.int32(fold_seed(ctx.seed, 0))

    def fresh_state():
        return make_state(seed32)

    params, state, opt_state = fresh_state()
    step = ff.make_train_step()
    batch = built["make_batch"](np.int32(fold_seed(ctx.seed, 2)))
    data = DevicePrefetcher(itertools.repeat(batch), machine=built["machine"],
                            depth=int(ctx.mix.get("prefetch_depth", 2)))
    jax.block_until_ready((params, batch))
    ctx.phases.mark("build_init")

    items = built["items_per_step"]
    fence_every = int(ctx.mix["fence_every"])
    try:
        for _ in range(int(ctx.mix["warmup_steps"])):
            params, state, opt_state, loss = step(params, state, opt_state,
                                                  *next(data))
        jax.block_until_ready(loss)

        t_open = ctx.window.open()
        fences = [(t_open, 0)]
        steps, stall0 = 0, data.stall_s
        while True:
            for _ in range(fence_every):
                with spans.span("bench:train_step"):
                    b = next(data)
                    params, state, opt_state, loss = step(
                        params, state, opt_state, *b)
                steps += 1
            with spans.span("bench:fence"):
                jax.block_until_ready(loss)
            now = time.perf_counter()
            # (the seconds it took to stop the profiler are not the
            # system's: fences lie on a clock with that pause cut out)
            fences.append((now - ctx.window.paused_s, steps * items))
            if ctx.window.trace_done(now, steps) \
                    and ctx.window.should_stop(now):
                break
        in_window = ctx.window.close()
        stall_s = data.stall_s - stall0
    finally:
        data.close()

    from benchmarks.stats import interval_step_seconds, whole_step_rate

    chips = int(ctx.mix["chips"])
    rate = whole_step_rate(fences)
    # ms a step between each two fences: a run that reads low shows where
    notes: Dict = {"steps": steps, "last_loss": float(loss),
                   "window_s": fences[-1][0] - fences[0][0],
                   "interval_step_ms": [round(1e3 * s, 3) for s in
                                        interval_step_seconds(fences,
                                                              items)]}
    del params, state, opt_state

    def check():
        problems, n = compare.train_step(ctx, built, fresh_state, step,
                                         batch)
        notes["correctness"] = n
        if not math.isfinite(notes["last_loss"]):
            problems.append(f"the window's last loss is "
                            f"{notes['last_loss']}: training diverged")
        return problems

    def after_trace(facts) -> Dict:
        """What only a traced run pays for: the compiled step's own
        account of its work (a second lowering, from jit's cache)."""
        p, s, o = fresh_state()
        compiled = step.lower(p, s, o, *batch).compile()
        ca = compiled.cost_analysis()
        out = {"xla_flops": float(ca.get("flops", 0.0)),
               "xla_bytes": float(ca.get("bytes accessed", 0.0))}
        regrid = ff.regrid_plan_summary()
        if regrid:
            out["regrid"] = regrid
        if plan_file:
            from flexflow_tpu.strategy import Strategy

            out["plan_predicted"] = getattr(Strategy.load(plan_file),
                                            "predicted", None)
        return out

    return {"end_to_end": {"train_items_per_s_per_chip": rate / chips},
            "attempted": steps, "failed": 0,
            "compile_in_window": in_window, "check": check,
            "after_trace": after_trace, "notes": notes,
            "facts": {"fences": fences, "items_per_step": items,
                      "steps": steps, "input_stall_s": stall_s,
                      "traced_steps": ctx.window.traced_work,
                      "flops": ctx.flops()}}
