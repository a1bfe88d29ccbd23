"""Serving driver — continuous-batching inference with latency-objective
strategies and queue-driven elastic autoscaling (serve/ package).

    python -m flexflow_tpu.apps.serve gpt --requests 32 --rate-qps 200 \\
        --max-new-tokens 4 -s serve_strat.json -obs-dir obs/
    python -m flexflow_tpu.apps.serve --smoke

The transformer family decodes autoregressively with continuous batching
and the sharded KV cache; CNN/NMT models get the batched forward-only
service (padded fixed-shape batches through DevicePrefetcher).  A
``-s``/``--strategy`` artifact — ideally one from ``apps/search.py
--serve`` (latency objective + ``__predicted__.serve`` block) — is
vetted by the static plan analyzer (verify/plan.py prices a serving
strategy forward-only with the KV cache charged) before anything runs.

Autoscaling: ``--serve-idle-boundaries N`` shrinks the mesh to
``--shrink-to`` devices after N consecutive idle decode boundaries;
``--serve-queue-hi D`` grows parked devices back when the arrival queue
reaches depth D.  Each resize re-searches under the latency objective on
the new mesh (utils/elastic.research_strategy) and live-regrids the
params.  **Drain contract**: SIGTERM/SIGINT stops admission, the
in-flight requests finish, queued-but-never-admitted requests are
reported ``unserved`` (never dropped), and the process EXITS 0.

stdout carries EXACTLY ONE JSON line —

    {"run_id": ..., "qps": ..., "p50_s": ..., "p99_s": ..., "resizes": ...}

(plus completed/unserved/dropped/devices/drained detail) — the same
single-record contract bench.py holds, asserted by ``make serve-smoke``.
Everything else (engine narration, resize logs, assertions) goes to
stderr.  ``--smoke`` runs the deterministic two-phase scenario: batched
replies must be bit-identical to the same requests served one-at-a-time,
and a gap-then-burst load must produce exactly one 8->6 shrink and one
6->8 grow with zero dropped requests and finite latencies.

Telemetry: ``-obs-dir`` streams serve_request / serve_batch /
serve_resize / serve_summary records (render with ``python -m
flexflow_tpu.apps.report serve <dir>``); ``-metrics-path`` exports the
ff_qps / ff_queue_depth / ff_latency_p50_s / ff_latency_p99_s /
ff_requests_total gauges.
"""

from __future__ import annotations

import json
import math
import os
import sys


def _err(*a, **kw):
    print(*a, file=sys.stderr, **kw)
    sys.stderr.flush()


def parse_args(argv):
    from flexflow_tpu.utils.flags import flag_stream

    opts = {
        "model": "gpt", "batch_size": 8, "max_batch": 0,
        "requests": 16, "rate_qps": 100.0, "max_new_tokens": 4,
        "prompt_len": 4, "seed": 0, "strategy": "", "dtype": "float32",
        "queue_hi": 0, "idle_boundaries": 0, "shrink_to": 0,
        "obs_dir": "", "run_id": "", "metrics_path": "",
        "step_time_s": 0.0, "tiny": False, "smoke": False,
        "prefill_devices": 0, "prefill_replicas": 1,
        "decode_replicas": 1, "disagg_smoke": False,
        "chaos_smoke": False,
    }
    args = list(argv)
    if args and not args[0].startswith("-"):
        opts["model"] = args.pop(0)
    for a, val in flag_stream(args):
        if a in ("-b", "--batch-size"):
            opts["batch_size"] = int(val())
        elif a == "--max-batch":
            opts["max_batch"] = int(val())
        elif a in ("-n", "--requests"):
            opts["requests"] = int(val())
        elif a == "--rate-qps":
            opts["rate_qps"] = float(val())
        elif a == "--max-new-tokens":
            opts["max_new_tokens"] = int(val())
        elif a == "--prompt-len":
            opts["prompt_len"] = int(val())
        elif a == "--seed":
            opts["seed"] = int(val())
        elif a in ("-s", "--strategy"):
            opts["strategy"] = val()
        elif a == "--dtype":
            opts["dtype"] = val()
        elif a == "--serve-queue-hi":
            opts["queue_hi"] = int(val())
        elif a == "--serve-idle-boundaries":
            opts["idle_boundaries"] = int(val())
        elif a == "--shrink-to":
            opts["shrink_to"] = int(val())
        elif a in ("-obs-dir", "--obs-dir"):
            opts["obs_dir"] = val()
        elif a in ("-run-id", "--run-id"):
            opts["run_id"] = val()
        elif a in ("-metrics-path", "--metrics-path"):
            opts["metrics_path"] = val()
        elif a == "--step-time-s":
            opts["step_time_s"] = float(val())
        elif a == "--tiny":
            opts["tiny"] = True
        elif a == "--smoke":
            opts["smoke"] = True
        elif a == "--serve-prefill-devices":
            # > 0 turns on disaggregated serving: the first N devices
            # become the prefill pool, the rest the decode pool
            opts["prefill_devices"] = int(val())
        elif a == "--serve-prefill-replicas":
            opts["prefill_replicas"] = int(val())
        elif a == "--serve-decode-replicas":
            opts["decode_replicas"] = int(val())
        elif a == "--disagg-smoke":
            opts["disagg_smoke"] = True
        elif a == "--chaos-smoke":
            opts["chaos_smoke"] = True
    return opts


def _build_lm(machine, *, batch, seed=0, dtype="float32", strategies=None,
              research_budget_s=10.0, tiny=False):
    """A serving TransformerLM plus the elastic rebuild factory that
    reconstructs it on a resized mesh (the same closure shape apps/lm.py
    hands fit()).  Default geometry matches apps/search.py's transformer
    (so a ``--serve`` search artifact names the same ops); ``tiny`` is
    the smoke's CPU-sized 2-layer GPT."""
    from flexflow_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)

    kw = dict(batch_size=batch, causal=True, seed=seed,
              compute_dtype=dtype, research_budget_s=research_budget_s)
    if tiny:
        kw.update(seq_length=16, num_layers=2, d_model=32, num_heads=4,
                  d_ff=128, vocab_size=64)
    cfg_t = TransformerConfig(**kw)
    model = TransformerLM(cfg_t, machine, strategies)

    def rebuild(ff_cfg, m):
        return TransformerLM(cfg_t, m, ff_cfg.strategies)

    return model, rebuild


def _build_forward(name, machine, batch, dtype, strategies):
    """A CNN/NMT model for the batched forward-only service, with the
    strategy passed at CONSTRUCTION (placement decisions are taken while
    the graph builds — setting config.strategies afterwards is too
    late)."""
    if name == "nmt":
        from flexflow_tpu.nmt.rnn_model import RnnConfig, RnnModel

        return RnnModel(RnnConfig(batch_size=batch, compute_dtype=dtype),
                        machine, strategies)
    from flexflow_tpu.apps.cnn import _builders
    from flexflow_tpu.config import FFConfig

    builders = _builders()
    if name not in builders:
        raise SystemExit(f"unknown model {name!r}")
    size = 299 if name.startswith("inception") else 224
    cfg = FFConfig(batch_size=batch, input_height=size, input_width=size,
                   compute_dtype=dtype)
    if strategies is not None:
        cfg.strategies = strategies
    return builders[name](cfg, machine)


def _forward_payloads(model, requests, seed):
    """Replace the loadgen token prompts with per-sample arrays matching
    the model's first input spec (image tensors for CNNs, full token
    rows for NMT) — the forward-only service pads these into the
    compiled batch rectangle."""
    import numpy as np

    in0 = model._inputs[0]
    shape = tuple(int(d) for d in in0.shape[1:])
    rng = np.random.RandomState(seed)
    for r in requests:
        if np.issubdtype(np.dtype(in0.dtype), np.integer):
            r.tokens = rng.randint(2, 64, size=shape).astype(in0.dtype)
        else:
            r.tokens = rng.uniform(-1.0, 1.0, size=shape).astype(in0.dtype)
    return requests


def _olog_metrics(opts, surface="serve"):
    from flexflow_tpu import obs
    from flexflow_tpu.obs.metrics import MetricsExporter

    meta = {"app": "serve", "model": opts["model"],
            "requests": opts["requests"], "seed": opts["seed"]}
    if opts["obs_dir"]:
        run_id = opts["run_id"] or obs.new_run_id()
        olog = obs.RunLog(
            os.path.join(opts["obs_dir"], f"{run_id}.jsonl"),
            run_id=run_id, surface=surface, meta=meta)
    else:
        olog = obs.NULL
    metrics = MetricsExporter(opts["metrics_path"], meta=meta) \
        if opts["metrics_path"] else None
    return olog, metrics


def _result_line(summary, olog) -> str:
    """The one stdout JSON line: the smoke-asserted keys first, detail
    after — one record, mirroring bench.py's contract."""
    rec = {
        "run_id": olog.run_id if olog.enabled else None,
        "qps": summary["qps"],
        "p50_s": summary["p50_s"],
        "p99_s": summary["p99_s"],
        "resizes": summary["resizes"],
        "requests": summary["requests"],
        "completed": summary["completed"],
        "unserved": summary["unserved"],
        "dropped": summary["dropped"],
        "devices": summary["devices"],
        "drained": summary["drained"],
    }
    return json.dumps(rec)


def _decode_pool_strategy(strategies, dbatch):
    """The decode pool's plan from a ``--serve --disagg`` artifact's
    inline ``serve.decode.strategies`` mapping, re-marked as a
    decode-phase artifact so verify/plan.py charges the KV ring to this
    pool (the prefill vet passes 0).  None when the artifact carries no
    per-phase decode plan."""
    from flexflow_tpu.strategy import ParallelConfig, Strategy

    serve = (getattr(strategies, "predicted", None) or {}).get("serve") \
        or {}
    dec = serve.get("decode") or {}
    if not dec.get("strategies"):
        return None
    out = Strategy({
        name: ParallelConfig(dims=tuple(int(d) for d in e["dims"]),
                             devices=tuple(int(d) for d in e["devices"]))
        for name, e in dec["strategies"].items()})
    out.predicted = {
        "objective": "decode",
        "serve": {"phase": "decode", "max_batch": dbatch,
                  # where ServeEngine(phase="decode") reads its
                  # searched virtual step time
                  "decode": {k: dec[k] for k in ("step_time_s",
                                                 "devices")
                             if k in dec}},
    }
    return out


def _disagg_run(opts, machine, strategies, olog, metrics, log) -> dict:
    """Disaggregated serving: carve the mesh at --serve-prefill-devices,
    build the prefill replicas + decode pool, vet each phase's plan,
    route the load (serve/router.py) under the drain contract."""
    from flexflow_tpu.serve.engine import DEFAULT_STEP_TIME_S, ServeEngine
    from flexflow_tpu.serve.loadgen import synthetic_requests
    from flexflow_tpu.serve.router import ServeRouter
    from flexflow_tpu.sim.search import decode_step_ratio
    from flexflow_tpu.utils.elastic import drain_scope
    from flexflow_tpu.verify.plan import check_plan

    n = machine.num_devices
    p = opts["prefill_devices"]
    pr, dr = max(1, opts["prefill_replicas"]), \
        max(1, opts["decode_replicas"])
    if not (0 < p < n):
        raise SystemExit(f"--serve-prefill-devices must split the "
                         f"{n}-device mesh, got {p}")
    if p % pr or (n - p) % dr:
        raise SystemExit(f"pools must split evenly: {p} prefill "
                         f"device(s) / {pr} replica(s), {n - p} decode "
                         f"device(s) / {dr} replica(s)")
    if opts["model"] not in ("transformer", "gpt", "bert"):
        raise SystemExit("disaggregated serving needs an autoregressive "
                         "LM (transformer/gpt/bert)")

    base_step = opts["step_time_s"] or DEFAULT_STEP_TIME_S
    prefill = []
    per = p // pr
    # each replica is its own mesh of `per` devices (shrink renumbers
    # ordinals 0..per-1), so the artifact's prefill plan must have been
    # searched at the PER-REPLICA slice, not the whole pool
    if strategies is not None:
        span = max((max(pc.devices) for pc in strategies.values()
                    if getattr(pc, "devices", None)), default=-1) + 1
        if span > per:
            raise SystemExit(
                f"prefill plan spans {span} device(s) but each of the "
                f"{pr} prefill replica(s) has {per}: search the prefill "
                f"phase at the per-replica slice (apps/search --devices "
                f"{per} --serve --disagg {n - p})")
    for j in range(pr):
        m = machine.shrink(list(range(j * per, (j + 1) * per)))
        model, _ = _build_lm(m, batch=max(1, opts["batch_size"]),
                             seed=opts["seed"], dtype=opts["dtype"],
                             strategies=strategies, tiny=opts["tiny"])
        if strategies is not None and j == 0:
            check_plan(model, strategies, m,
                       label=os.path.basename(opts["strategy"]))
        prefill.append(ServeEngine(
            model, None, olog=olog, metrics=metrics, log=log,
            step_time_s=opts["step_time_s"] or None, phase="prefill"))
    decode = []
    dper = (n - p) // dr
    dbatch = max(1, opts["batch_size"])
    dstrat = _decode_pool_strategy(strategies, dbatch)
    if dstrat is not None:
        span = max((max(pc.devices) for pc in dstrat.values()
                    if getattr(pc, "devices", None)), default=-1) + 1
        if span > dper:
            raise SystemExit(
                f"decode plan spans {span} device(s) but each of the "
                f"{dr} decode replica(s) has {dper}: search the decode "
                f"companion at the per-replica slice (apps/search "
                f"--serve --disagg {dper})")
    for j in range(dr):
        m = machine.shrink(list(range(p + j * dper, p + (j + 1) * dper)))
        model, _ = _build_lm(m, batch=dbatch, seed=opts["seed"],
                             dtype=opts["dtype"], strategies=dstrat,
                             tiny=opts["tiny"])
        if dstrat is not None and j == 0:
            check_plan(model, dstrat, m,
                       label=f"{os.path.basename(opts['strategy'])}"
                             f"[decode]")
        step = None if dstrat is not None and opts["step_time_s"] == 0 \
            else base_step * decode_step_ratio(model)
        decode.append(ServeEngine(
            model, None, olog=olog, metrics=metrics, log=log,
            step_time_s=step, phase="decode"))
    router = ServeRouter(prefill, decode, olog=olog, metrics=metrics,
                         log=log)
    vocab = getattr(getattr(prefill[0].model, "t", None),
                    "vocab_size", 64)
    requests = synthetic_requests(
        opts["requests"], seed=opts["seed"], rate_qps=opts["rate_qps"],
        vocab_size=vocab, prompt_len=opts["prompt_len"],
        max_new_tokens=opts["max_new_tokens"])
    with drain_scope(log=log) as drain:
        return router.run(requests, drain=drain)


def serve_run(opts, log=_err) -> dict:
    """One serving run with the production wiring: plan-vetted strategy,
    obs + metrics, drain handler installed, autoscale watermarks from
    the flags.  Returns the engine summary (caller prints the line)."""
    from flexflow_tpu.machine import MachineModel
    from flexflow_tpu.serve.engine import ServeEngine
    from flexflow_tpu.serve.loadgen import synthetic_requests
    from flexflow_tpu.strategy import Strategy
    from flexflow_tpu.utils.elastic import drain_scope
    from flexflow_tpu.verify.plan import check_plan

    machine = MachineModel()
    batch = opts["max_batch"] or opts["batch_size"]
    strategies = None
    if opts["strategy"]:
        strategies = Strategy.load(opts["strategy"])

    if opts["prefill_devices"] > 0:
        olog, metrics = _olog_metrics(opts)
        summary = _disagg_run(opts, machine, strategies, olog, metrics,
                              log)
        summary["_olog"] = olog
        olog.close()
        return summary

    if opts["model"] in ("transformer", "gpt", "bert"):
        model, rebuild = _build_lm(
            machine, batch=batch, seed=opts["seed"],
            dtype=opts["dtype"], strategies=strategies,
            tiny=opts["tiny"])
        decode = True
    else:
        model = _build_forward(opts["model"], machine, batch,
                               opts["dtype"], strategies)
        rebuild = None
        decode = False
    if strategies is not None:
        # serving strategies are vetted forward-only with the KV cache
        # charged (verify/plan.py detects the latency objective)
        check_plan(model, strategies, machine,
                   label=os.path.basename(opts["strategy"]))

    olog, metrics = _olog_metrics(opts)
    engine = ServeEngine(
        model, rebuild, olog=olog, metrics=metrics, log=log,
        step_time_s=opts["step_time_s"] or None,
        queue_hi=opts["queue_hi"],
        idle_boundaries=opts["idle_boundaries"],
        shrink_to=opts["shrink_to"])
    vocab = getattr(getattr(model, "t", None), "vocab_size", 64)
    requests = synthetic_requests(
        opts["requests"], seed=opts["seed"], rate_qps=opts["rate_qps"],
        vocab_size=vocab, prompt_len=opts["prompt_len"],
        max_new_tokens=opts["max_new_tokens"])
    if not decode:
        _forward_payloads(model, requests, opts["seed"])
    with drain_scope(log=log) as drain:
        summary = engine.run(requests, drain=drain) if decode \
            else engine.run_forward(requests, drain=drain)
    summary["_olog"] = olog
    # the served requests with their replies, for callers that check
    # WHAT was answered (chip_smoke.py); never printed
    summary["_requests"] = requests
    olog.close()
    return summary


# ---------------------------------------------------------------------------
# the deterministic --smoke scenario (make serve-smoke)


def _smoke_equivalence(log) -> None:
    """Batching on vs off must not change a single reply: the same five
    requests served through a full 8-slot continuous batch and through a
    1-slot engine on a 1-device mesh produce bit-identical token
    sequences (row-independent decode + pad-inert rectangle)."""
    from flexflow_tpu.machine import MachineModel
    from flexflow_tpu.serve.engine import ServeEngine
    from flexflow_tpu.serve.loadgen import synthetic_requests

    def replies(batch, machine):
        model, _ = _build_lm(machine, batch=batch, seed=0, tiny=True)
        eng = ServeEngine(model, None, log=lambda *a: None)
        reqs = synthetic_requests(5, seed=0, rate_qps=1000.0,
                                  vocab_size=64, prompt_len=4,
                                  max_new_tokens=3)
        eng.run(reqs)
        return {r.rid: list(r.reply) for r in reqs}

    m8 = MachineModel()
    m1 = m8.shrink([0])
    a = replies(8, m8)
    b = replies(1, m1)
    assert a == b, \
        f"batched replies must be bit-identical to single-request " \
        f"replies: {a} vs {b}"
    log(f"serve-smoke equivalence ok: {len(a)} replies bit-identical "
        f"with batching on (8 slots / 8 devices) vs off (1 slot / "
        f"1 device)")


def _smoke_lifecycle(opts, log) -> dict:
    """Gap-then-burst load against the autoscaling engine: 6 early
    requests, a 30-virtual-second idle gap (shrink 8 -> 6), then a
    40-request burst (queue-depth grow 6 -> 8).  Asserts exactly one
    resize per direction, zero unserved/dropped, finite latencies."""
    from flexflow_tpu.machine import MachineModel
    from flexflow_tpu.obs.report import summarize
    from flexflow_tpu.serve.engine import ServeEngine
    from flexflow_tpu.serve.loadgen import synthetic_requests
    from flexflow_tpu import obs

    machine = MachineModel()
    model, rebuild = _build_lm(machine, batch=24, seed=0,
                               research_budget_s=2.0, tiny=True)
    olog, metrics = _olog_metrics(opts)
    engine = ServeEngine(model, rebuild, olog=olog, metrics=metrics,
                         log=log, queue_hi=4, idle_boundaries=3,
                         shrink_to=6)
    early = synthetic_requests(6, seed=0, rate_qps=500.0, vocab_size=64,
                               prompt_len=4, max_new_tokens=3)
    burst = synthetic_requests(40, seed=1, rate_qps=2000.0,
                               vocab_size=64, prompt_len=4,
                               max_new_tokens=3,
                               start_v=early[-1].arrival_v + 30.0)
    for i, r in enumerate(burst):
        r.rid = 100 + i
    summary = engine.run(early + burst)

    dirs = [(r["direction"], r["from_devices"], r["to_devices"])
            for r in engine.resizes]
    assert dirs == [("shrink", 8, 6), ("grow", 6, 8)], \
        f"expected exactly one 8->6 shrink then one 6->8 grow, got {dirs}"
    assert summary["completed"] == 46 and summary["unserved"] == 0 \
        and summary["dropped"] == 0, summary
    assert math.isfinite(summary["p50_s"]) \
        and math.isfinite(summary["p99_s"]), summary
    assert summary["devices"] == 8, \
        f"run must END on the full mesh after the grow: {summary}"

    if olog.enabled:
        events = list(obs.read_run(olog.path))
        srs = [e for e in events if e["kind"] == "serve_resize"]
        assert [(r["direction"], r["from_devices"], r["to_devices"])
                for r in srs] == dirs, srs
        s = summarize(events)
        assert s.get("serve", {}).get("summary", {}).get("dropped") == 0, \
            s.get("serve")
        # the smoke's obs dir must render through `report serve`
        from flexflow_tpu.apps.report import serve_main

        rendered = []
        rc = serve_main([olog.path], log=lambda m: rendered.append(m))
        assert rc == 0 and rendered \
            and "latency histogram" in rendered[0], \
            f"report serve must render the latency histogram: rc={rc}"
        for line in rendered:
            log(line)
    log(f"serve-smoke lifecycle ok: {summary['completed']} served, "
        f"resizes {dirs}, p50 {summary['p50_s'] * 1e3:.1f} ms, "
        f"p99 {summary['p99_s'] * 1e3:.1f} ms")
    summary["_olog"] = olog
    olog.close()
    return summary


class _DrainAfter(dict):
    """A deterministic stand-in for the SIGTERM drain flag: reads as
    not-requested for the first ``after`` checks, then requested — the
    router polls once per event-loop boundary, so the drain lands
    mid-run at a fixed virtual instant regardless of wall clock."""

    def __init__(self, after: int):
        super().__init__()
        self.after = int(after)
        self.checks = 0

    def get(self, key, default=None):
        if key == "requested":
            self.checks += 1
            return self.checks > self.after
        return super().get(key, default)


def _smoke_disagg(opts, log) -> dict:
    """The deterministic disaggregation scenario (make disagg-smoke):
    two 2-device prefill replicas + one 4-device decode pool on the
    8-device CPU mesh, serving a seeded multi-turn ``session`` load.
    Asserts (1) every routed reply is BIT-IDENTICAL to the same request
    served by the single-pool engine, (2) the run exercises the router
    for real — >= 1 KV handoff and >= 1 session-affinity hit — and
    (3) a mid-run drain finishes in-flight work, reports the rest
    unserved, and returns cleanly (exit 0)."""
    from flexflow_tpu.machine import MachineModel
    from flexflow_tpu.obs.trace import (chrome_trace, serve_trace_events,
                                        validate_trace)
    from flexflow_tpu.serve.engine import (DEFAULT_STEP_TIME_S,
                                           ServeEngine)
    from flexflow_tpu.serve.loadgen import patterned_requests
    from flexflow_tpu.serve.router import ServeRouter
    from flexflow_tpu.sim.search import decode_step_ratio
    from flexflow_tpu import obs

    machine = MachineModel()

    def build_pools(olog, metrics):
        prefill = []
        for j in range(2):
            m = machine.shrink([2 * j, 2 * j + 1])
            model, _ = _build_lm(m, batch=2, seed=0, tiny=True)
            prefill.append(ServeEngine(
                model, None, olog=olog, metrics=metrics,
                log=lambda *a: None, step_time_s=DEFAULT_STEP_TIME_S,
                phase="prefill"))
        dm = machine.shrink([4, 5, 6, 7])
        dmodel, _ = _build_lm(dm, batch=4, seed=0, tiny=True)
        decode = [ServeEngine(
            dmodel, None, olog=olog, metrics=metrics,
            log=lambda *a: None,
            step_time_s=DEFAULT_STEP_TIME_S * decode_step_ratio(dmodel),
            phase="decode")]
        return prefill, decode

    def session_load():
        return patterned_requests(12, seed=0, rate_qps=50.0,
                                  pattern="session", vocab_size=64,
                                  prompt_len=6, max_new_tokens=4)

    olog, metrics = _olog_metrics(opts)
    prefill, decode = build_pools(olog, metrics)
    router = ServeRouter(prefill, decode, olog=olog, metrics=metrics,
                         log=log)
    reqs = session_load()
    summary = router.run(reqs)
    routed = {r.rid: list(r.reply) for r in reqs}

    single_model, _ = _build_lm(machine, batch=8, seed=0, tiny=True)
    single = ServeEngine(single_model, None, log=lambda *a: None)
    sreqs = session_load()
    single.run(sreqs)
    expected = {r.rid: list(r.reply) for r in sreqs}
    assert routed == expected, \
        f"routed replies must be bit-identical to the single-pool " \
        f"engine's: {routed} vs {expected}"
    assert summary["handoffs"] >= 1 and summary["affinity_hits"] >= 1, \
        f"smoke must exercise the router: {summary['handoffs']} " \
        f"handoff(s), {summary['affinity_hits']} affinity hit(s)"
    assert summary["completed"] == 12 and summary["unserved"] == 0, \
        summary
    assert summary["kv_refetches"] == 0, summary

    # mid-run drain: fresh pools, the flag flips after three event-loop
    # boundaries — in-flight prefills hand off and decode to completion,
    # everything still queued or undispatched is unserved, exit clean
    prefill2, decode2 = build_pools(olog, metrics)
    router2 = ServeRouter(prefill2, decode2, olog=olog,
                          metrics=metrics, log=log)
    dsum = router2.run(session_load(), drain=_DrainAfter(3))
    assert dsum["drained"], dsum
    assert dsum["completed"] + dsum["unserved"] == 12 \
        and dsum["unserved"] >= 1, dsum

    if olog.enabled:
        events = list(obs.read_run(olog.path))
        kinds = {e["kind"] for e in events}
        assert {"serve_handoff", "router_summary"} <= kinds, kinds
        errors = validate_trace(chrome_trace(serve_trace_events(events)))
        assert not errors, errors
        from flexflow_tpu.apps.report import serve_main

        rendered = []
        rc = serve_main([olog.path], log=lambda m: rendered.append(m))
        assert rc == 0 and rendered, "report serve must render"
        for line in rendered:
            log(line)
    log(f"disagg-smoke ok: {summary['completed']} routed replies "
        f"bit-identical to single-pool, {summary['handoffs']} "
        f"handoff(s), {summary['affinity_hits']} affinity hit(s); "
        f"drain left {dsum['unserved']} unserved and exited clean")
    summary["_olog"] = olog
    olog.close()
    return summary


#: the seeded chaos the recovery phase injects: the decode pool's
#: third health-check probe kills a replica mid-decode (in-flight work
#: re-prefills, queued handoffs retransmit), and the fifth KV transfer
#: is dropped on the wire (retransmit) — both recover under the
#: default retry budget with zero lost requests
CHAOS_SMOKE_SPEC = "replica_crash@3,handoff_drop@5"


def _smoke_chaos(opts, log) -> dict:
    """The deterministic resilience scenario (make chaos-smoke), two
    phases on the same pool shape (two 2-device prefill replicas + two
    2-device decode replicas):

    1. **equivalence** — the full resilience machinery ARMED (injector
       installed with an empty spec, RetryPolicy, AdmissionGate) but
       never firing must be byte-inert: replies and summary counters
       bit-identical to a plain router on the same load, and to the
       single-pool engine;
    2. **recovery** — ``CHAOS_SMOKE_SPEC`` kills decode[0] at its third
       health-check probe and drops the fifth KV handoff on the wire:
       every admitted request still completes with BIT-IDENTICAL
       replies (re-prefill regenerates the same greedy tokens), >= 1
       kv_rebuild, exactly 1 replica_down, >= 2 serve_retry records,
       zero unserved/failed/shed — bounded degradation, nothing
       silently lost — and the obs stream renders + traces clean."""
    from flexflow_tpu.machine import MachineModel
    from flexflow_tpu.obs.trace import (chrome_trace, serve_trace_events,
                                        validate_trace)
    from flexflow_tpu.serve.engine import (DEFAULT_STEP_TIME_S,
                                           ServeEngine)
    from flexflow_tpu.serve.loadgen import patterned_requests
    from flexflow_tpu.serve.router import AdmissionGate, ServeRouter
    from flexflow_tpu.sim.search import decode_step_ratio
    from flexflow_tpu.utils.faultinject import (FaultInjector,
                                                install_scoped)
    from flexflow_tpu.utils.retry import RetryPolicy
    from flexflow_tpu import obs

    machine = MachineModel()

    def build_pools(olog, metrics):
        prefill, decode = [], []
        for j in range(2):
            m = machine.shrink([2 * j, 2 * j + 1])
            model, _ = _build_lm(m, batch=2, seed=0, tiny=True)
            prefill.append(ServeEngine(
                model, None, olog=olog, metrics=metrics,
                log=lambda *a: None, step_time_s=DEFAULT_STEP_TIME_S,
                phase="prefill"))
        for j in range(2):
            dm = machine.shrink([4 + 2 * j, 5 + 2 * j])
            dmodel, _ = _build_lm(dm, batch=2, seed=0, tiny=True)
            decode.append(ServeEngine(
                dmodel, None, olog=olog, metrics=metrics,
                log=lambda *a: None,
                step_time_s=DEFAULT_STEP_TIME_S
                * decode_step_ratio(dmodel),
                phase="decode"))
        return prefill, decode

    def session_load():
        return patterned_requests(12, seed=0, rate_qps=50.0,
                                  pattern="session", vocab_size=64,
                                  prompt_len=6, max_new_tokens=4)

    def resilient_router(olog, metrics):
        prefill, decode = build_pools(olog, metrics)
        return ServeRouter(prefill, decode, olog=olog, metrics=metrics,
                           log=log, retry_policy=RetryPolicy(),
                           admission=AdmissionGate())

    # ground truth: the single-pool engine's replies for the same load
    single_model, _ = _build_lm(machine, batch=8, seed=0, tiny=True)
    single = ServeEngine(single_model, None, log=lambda *a: None)
    sreqs = session_load()
    single.run(sreqs)
    expected = {r.rid: list(r.reply) for r in sreqs}

    # phase 1: armed machinery must be byte-inert.  Baseline = a plain
    # router (no injector / retry / gate); armed = the full resilience
    # stack with an EMPTY fault spec.
    prefill0, decode0 = build_pools(obs.NULL, None)
    plain = ServeRouter(prefill0, decode0, log=lambda *a: None)
    breqs = session_load()
    bsum = plain.run(breqs)
    baseline = {r.rid: list(r.reply) for r in breqs}

    olog, metrics = _olog_metrics(opts)
    router = resilient_router(olog, metrics)
    idle = FaultInjector("")  # armed-but-idle: enabled, never fires
    restore = install_scoped(idle)
    try:
        areqs = session_load()
        asum = router.run(areqs)
    finally:
        restore()
    armed = {r.rid: list(r.reply) for r in areqs}
    assert armed == baseline == expected, \
        f"armed-but-idle resilience machinery must be byte-inert: " \
        f"{armed} vs {baseline} vs {expected}"
    assert idle.fired() == 0, \
        f"an empty spec must never fire: {idle.fired()}"
    assert asum["retries"] == asum["shed"] == asum["failed"] == 0 \
        and asum["replica_down"] == 0 and asum["kv_rebuilds"] == 0, asum
    inert_keys = ("completed", "unserved", "shed", "failed", "handoffs",
                  "affinity_hits", "kv_refetches", "steps", "p50_s",
                  "p99_s", "ttft_p50_s", "virtual_s")
    diverged = {k: (bsum[k], asum[k]) for k in inert_keys
                if bsum[k] != asum[k]}
    assert not diverged, \
        f"armed summary diverged from the plain router's: {diverged}"
    log(f"chaos-smoke equivalence ok: armed-but-idle machinery "
        f"byte-inert ({asum['completed']} replies bit-identical to "
        f"plain router and single pool)")

    # phase 2: the seeded chaos — recovery must be total
    router2 = resilient_router(olog, metrics)
    inj = FaultInjector(CHAOS_SMOKE_SPEC, olog=olog)
    restore2 = install_scoped(inj)
    try:
        creqs = session_load()
        csum = router2.run(creqs)
    finally:
        restore2()
    chaos = {r.rid: list(r.reply) for r in creqs if r.reply is not None}
    assert chaos == expected, \
        f"recovered replies must be bit-identical to the fault-free " \
        f"run: {chaos} vs {expected}"
    assert csum["completed"] == 12 and csum["unserved"] == 0 \
        and csum["failed"] == 0 and csum["shed"] == 0, csum
    assert csum["completed"] + csum["unserved"] + csum["shed"] \
        + csum["failed"] == csum["requests"] == 12, csum
    assert csum["replica_down"] == 1, csum
    assert csum["kv_rebuilds"] >= 1, \
        f"the crash must force >= 1 KV re-materialization: {csum}"
    assert csum["retries"] >= 2, csum
    assert csum["replicas_live"] == 2, \
        f"the crashed replica must be back by run end: {csum}"
    assert inj.fired("replica_crash") == 1 \
        and inj.fired("handoff_drop") == 1, \
        f"spec {CHAOS_SMOKE_SPEC!r} must fire both faults: " \
        f"{inj.fired('replica_crash')} crash(es), " \
        f"{inj.fired('handoff_drop')} drop(s)"

    if olog.enabled:
        events = list(obs.read_run(olog.path))
        downs = [e for e in events if e["kind"] == "replica_down"]
        retries = [e for e in events if e["kind"] == "serve_retry"]
        rebuilds = [e for e in events if e["kind"] == "kv_rebuild"]
        assert len(downs) == 1 and downs[0]["replica"] == 0, downs
        assert len(retries) == csum["retries"] and len(retries) >= 2, \
            retries
        assert len(rebuilds) == csum["kv_rebuilds"] >= 1, rebuilds
        assert not any(e["kind"] == "serve_fault" for e in events)
        errors = validate_trace(chrome_trace(serve_trace_events(events)))
        assert not errors, errors
        from flexflow_tpu.apps.report import serve_main

        rendered = []
        rc = serve_main([olog.path], log=lambda m: rendered.append(m))
        assert rc == 0 and rendered, "report serve must render"
        assert any("resilience:" in ln for ln in rendered), \
            "report serve must render the resilience line"
        for line in rendered:
            log(line)
    log(f"chaos-smoke recovery ok: {CHAOS_SMOKE_SPEC!r} -> "
        f"{csum['completed']}/12 complete with bit-identical replies, "
        f"{csum['replica_down']} replica down, {csum['kv_rebuilds']} "
        f"KV rebuild(s), {csum['retries']} retry(ies), 0 lost")
    csum["_olog"] = olog
    olog.close()
    return csum


def _require_mesh() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    if jax.device_count() != 8:
        raise SystemExit(
            f"serve --smoke needs the 8-device simulated mesh "
            f"(XLA_FLAGS=--xla_force_host_platform_device_count=8), "
            f"got {jax.device_count()} devices")


def smoke(opts, log=_err) -> dict:
    _require_mesh()
    _smoke_equivalence(log)
    return _smoke_lifecycle(opts, log)


def disagg_smoke(opts, log=_err) -> dict:
    _require_mesh()
    return _smoke_disagg(opts, log)


def chaos_smoke(opts, log=_err) -> dict:
    _require_mesh()
    return _smoke_chaos(opts, log)


def main(argv=None, log=_err) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    opts = parse_args(argv)
    smoker = chaos_smoke if opts["chaos_smoke"] \
        else (disagg_smoke if opts["disagg_smoke"]
              else (smoke if opts["smoke"] else None))
    if smoker is not None and not opts["obs_dir"]:
        import tempfile

        with tempfile.TemporaryDirectory(prefix="ff-serve-smoke-") as td:
            opts["obs_dir"] = os.path.join(td, "obs")
            summary = smoker(opts, log)
            print(_result_line(summary, summary.pop("_olog")))
            return 0
    summary = smoker(opts, log) if smoker is not None \
        else serve_run(opts, log)
    print(_result_line(summary, summary.pop("_olog")))
    return 0


if __name__ == "__main__":
    from flexflow_tpu.utils.chip import enable_compile_cache

    enable_compile_cache()  # before the first compile
    sys.exit(main())
