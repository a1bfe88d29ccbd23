"""What every cell shares: finding the cell's files by the names in
BENCHMARK.json, one clock for the set-up phases, the compile meter, the
``bench:`` spans, the profiler window, the per-layer readers and the last
line.  It knows no cell, configuration, mix or metric by name: a driver
is found by the mix's ``kind``, a builder by the configuration's
``builder``, a reader by the metric's name.
"""

from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import os
import sys
import time
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


# --------------------------------------------------------------------------
# finding things by name


def load_cell(root: str, workload: str) -> Dict:
    """The cell's entry, its configuration and its mix, and the metrics
    it reports, from ``<root>/BENCHMARK.json`` and the files it names."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json (has {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    bdir = os.path.join(root, os.path.dirname(
        os.path.dirname(cfg_entry["file"])))
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    from benchmarks.traffic_gen import load_mix

    mix = load_mix(cell["traffic"], os.path.join(bdir, "traffic"))
    if int(mix["chips"]) != int(cell["chips"]):
        raise SystemExit(f"benchmark: {workload}: BENCHMARK.json asks for "
                         f"{cell['chips']} chip(s), the mix for "
                         f"{mix['chips']}")

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return {"cell": cell, "config": config, "mix": mix, "dir": bdir,
            "run_seconds": bench["run_seconds"],
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def load_by_name(directory: str, name: str):
    """The module ``<directory>/<name>.py``.  By path, because a metric's
    name has dots in it and a later PR's directory is not a package."""
    path = os.path.join(directory, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no file {path}")
    key = "benchmarks._by_name." + os.path.relpath(path, CHECKOUT) \
        .replace(os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def load_peaks(device_kind: str) -> Dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise SystemExit(f"benchmark: no peaks for device_kind "
                         f"{device_kind!r} in benchmarks/peaks.json; a "
                         f"device that is not in the table is an error, "
                         f"not a default")
    return table[device_kind]


def op_params(ff, params) -> Dict:
    """{op name: {leaf: array}} in each op's own logical shapes, whatever
    storage the plan gave them: how the comparison hands the system's
    weights to the plain reference."""
    out = {}
    for op in ff.layers:
        if op.param_key in params and op.param_key == op.name:
            p = ff._member_params(params, op)
            if p:
                out[op.name] = p
    return out


# --------------------------------------------------------------------------
# clocks, spans, compile events


class Phases:
    """Seconds by set-up phase on ONE clock that starts at the first
    line of run.py.  ``mark(name)`` closes the phase that ran since the
    last mark."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self._last = t_start
        self.seconds: Dict[str, float] = {}

    def mark(self, name: str) -> float:
        now = time.perf_counter()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self._last
        self._last = now
        return now


class Spans:
    """The benchmark's own spans around its calls into the layers, named
    ``bench:<what>``: kept in memory on the host clock, and, while the
    profiler runs, written into its trace on the device events' clock
    (``jax.profiler.TraceAnnotation``) so that idle gaps can be charged
    to what the host was doing."""

    def __init__(self):
        self.records: Dict[str, List[tuple]] = {}
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.records.setdefault(name, []).append((t0, t1))


class CompileMeter:
    """Seconds JAX spent in backend compilation (a persistent-cache hit
    counts its retrieval), the cache's hits and misses, and how many
    compilations there were, from JAX's monitoring events (copied from
    chip_smoke.py's CompileMeter, PR 21)."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> Dict:
        return {"compile_s": self.compile_s, "compiles": self.compiles,
                "hits": self.hits, "misses": self.misses}


class Window:
    """The measured window of one run, and inside it (``--trace 1``) the
    traced one.  A driver calls ``open()`` when set-up is over,
    ``should_stop(now)`` at each fence, ``close()`` at the last one."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.t_open = None
        self.compile_at_open = None
        self.trace_dir = None
        self.traced_work = None
        self.paused_s = 0.0      # spent stopping the profiler mid-window
        self._tracing = False
        self._window_ann = None

    def open(self) -> float:
        ctx = self.ctx
        self.compile_at_open = ctx.meter.snapshot()
        self.t_open = ctx.phases.mark("warmup")
        if ctx.trace:
            # starting the profiler is set-up of the traced run alone
            self._start_trace()
            self.t_open = ctx.phases.mark("trace_start")
        ctx.setup_s = self.t_open - ctx.phases.t_start
        return self.t_open

    def _start_trace(self):
        import jax
        import shutil

        self.trace_dir = os.path.join(self.ctx.scratch, "trace")
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # the bench: spans are TraceMes
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._tracing = True
        self.ctx.spans.tracing = True
        self._window_ann = jax.profiler.TraceAnnotation("bench:trace_window")
        self._window_ann.__enter__()
        self.t_trace_open = time.perf_counter()

    def trace_done(self, now: float, work: float = 0.0) -> bool:
        """Called at a fence with the work done so far: ends the traced
        window once it has lasted the mix's ``trace_seconds``.  True
        while no trace is running."""
        if not self._tracing:
            return True
        if now - self.t_trace_open < self.ctx.trace_seconds:
            return False
        self.traced_work = work
        self.stop_trace()
        self.paused_s += time.perf_counter() - now
        return True

    def stop_trace(self):
        if not self._tracing:
            return
        import jax

        self._window_ann.__exit__(None, None, None)
        self.t_trace_close = time.perf_counter()
        self.ctx.spans.tracing = False
        jax.profiler.stop_trace()
        self._tracing = False

    def should_stop(self, now: float) -> bool:
        return now - self.t_open >= self.ctx.seconds

    def close(self) -> Dict:
        self.stop_trace()
        self.ctx.phases.mark("window")
        after = self.ctx.meter.snapshot()
        return {k: after[k] - self.compile_at_open[k] for k in after}


class Context:
    """What a driver is handed."""

    def __init__(self, args, cell: Dict, phases: Phases):
        self.workload = args.workload
        self.seed = int(args.seed)
        self.trace = bool(args.trace)
        self.rehearsal = bool(args.cpu_rehearsal)
        self.cell = cell
        self.config = dict(cell["config"])
        self.mix = dict(cell["mix"])
        if self.rehearsal:
            # tiny sizes, stated in the files themselves
            self.config.update(self.config.get("rehearsal", {}))
            self.mix.update(self.mix.get("rehearsal", {}))
        self.seconds = float(args.seconds if args.seconds is not None
                             else cell["run_seconds"])
        self.trace_seconds = min(float(self.mix.get("trace_seconds", 5.0)),
                                 self.seconds)
        self.phases = phases
        self.spans = Spans()
        self.meter: Optional[CompileMeter] = None
        self.device: Dict = {}
        self.setup_s: Optional[float] = None
        # what a checkout keeps between runs, beside the compile cache
        self.keep = os.path.join(CHECKOUT, ".bench_cache")
        self.scratch = os.path.join(self.keep, "scratch", self.workload)
        os.makedirs(self.scratch, exist_ok=True)
        self.window = Window(self)

    def builder(self):
        return load_by_name(os.path.join(self.cell["dir"], "builders"),
                            self.config["builder"])

    def reference(self):
        return load_by_name(os.path.join(self.cell["dir"], "reference"),
                            self.config["name"])

    def flops(self):
        return load_by_name(os.path.join(self.cell["dir"], "flops"),
                            self.config["name"])


# --------------------------------------------------------------------------
# the run


def _say(what: str, payload: Dict) -> None:
    print(f"benchmark: {what} " + json.dumps(payload), flush=True)


def _device_record(chips: int, rehearsal: bool) -> Dict:
    """The device as JAX reports it; refuses (no result, non-zero) any
    platform but the TPU and fewer chips than the cell asks for."""
    from flexflow_tpu.utils.chip import require_tpu

    dev = require_tpu("benchmarks/run.py", rehearsal)
    if dev["count"] < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chip(s), JAX "
                         f"found {dev['count']}")
    return dev


def memory_peak_bytes(devices) -> Optional[int]:
    """Peak HBM on the fullest chip.  On this runtime
    ``peak_bytes_in_use`` counts the buffers a process holds (arguments,
    results) and NOT the region a running program's temporaries live in,
    which is ``peak_bytes_reserved`` (chip runs of PR 24: Inception's
    step reads 0.66 GB in use and 8.1 GB reserved); the peak is their
    sum.  A run is a fresh process, so the lifetime peak is the run's."""
    best = None
    for d in devices:
        s = d.memory_stats() or {}
        if "peak_bytes_in_use" in s:
            v = int(s["peak_bytes_in_use"]) + int(
                s.get("peak_bytes_reserved", 0))
            best = v if best is None else max(best, v)
    return best


def _trace_facts(ctx: Context) -> Optional[Dict]:
    if not ctx.window.trace_dir:
        return None
    from benchmarks import trace_reduce

    files = sorted(glob.glob(os.path.join(ctx.window.trace_dir, "**",
                                          "*.xplane.pb"), recursive=True))
    if not files:
        return None
    return trace_reduce.reduce_events(trace_reduce.load_xplane(files[-1]))


def read_per_layer(cell: Dict, facts: Dict) -> Dict:
    """The cell's per-layer metrics: BENCHMARK.json alone says which
    (an entry with no ``workloads`` key, or one that lists the cell), and
    each is read by the file of its name.  A reader that finds nothing
    to read returns None and its metric is left out."""
    readers = os.path.join(cell["dir"], "layer_metrics")
    metrics = {}
    for m in cell["per_layer"]:
        value = load_by_name(readers, m["name"]).read(facts)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def run(args, t_start: float) -> int:
    phases = Phases(t_start)
    cell = load_cell(args.root, args.workload)
    ctx = Context(args, cell, phases)
    chips = int(ctx.mix["chips"])
    if ctx.rehearsal and chips > 1:
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={chips}")
    driver = load_by_name(os.path.join(cell["dir"], "drivers"),
                          ctx.mix["kind"])

    import jax  # the first touch of JAX: after the cell's files are read

    phases.mark("import")
    ctx.device = _device_record(chips, ctx.rehearsal)
    if not ctx.rehearsal:
        from flexflow_tpu.utils.chip import enable_compile_cache

        enable_compile_cache()
        # every program is kept, however quick its compile: a warm run
        # must find all of them, or its set-up holds compilations
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # weights and inputs are drawn in set-up, on the device: the TPU's own
    # generator makes them in a sixth of threefry's time (Inception's
    # 190 leaves and its batch: 31 s against 5 s, chip runs of PR 24),
    # and no measured step draws a number
    jax.config.update("jax_default_prng_impl", "rbg")
    ctx.meter = CompileMeter()
    devices = jax.devices()[:chips]
    ctx.devices = devices
    if ctx.device["count"] != chips:
        # a cell runs on exactly the chips it asks for
        ctx.device = dict(ctx.device, count=chips)
    phases.mark("device_init")

    result = driver.run(ctx)   # build_init, plan, warm-up, window

    in_window = result["compile_in_window"]
    problems = list(result.get("problems", []))
    if in_window["compiles"]:
        problems.append(f"{in_window['compiles']} compilation(s) inside "
                        f"the measured window")
    with ctx.spans.span("bench:correctness"):
        problems += result["check"]()
    phases.mark("correctness")

    facts = dict(result["facts"])
    facts.update(phases=phases.seconds, setup_s=ctx.setup_s,
                 compile_at_open=ctx.window.compile_at_open,
                 spans=ctx.spans.records, config=ctx.config, mix=ctx.mix,
                 device=ctx.device, chips=chips,
                 memory_peak_bytes=memory_peak_bytes(devices))
    if not ctx.rehearsal:
        facts["peaks"] = load_peaks(ctx.device["kind"])
    if ctx.trace:
        facts["trace"] = _trace_facts(ctx)
        if result.get("after_trace"):
            facts.update(result["after_trace"](facts))
    phases.mark("trace")

    _say("phases", {k: round(v, 3) for k, v in phases.seconds.items()})
    _say("compile", {"at_window_open": ctx.window.compile_at_open,
                     "in_window": in_window, "total": ctx.meter.snapshot()})
    if facts.get("trace"):
        calls = sorted(((k, v) for k, v in facts["trace"]["op_s"].items()
                        if k.endswith("|custom-call")), key=lambda kv: -kv[1])
        result.setdefault("notes", {})["custom_calls"] = [
            [k.split("|")[0], v] for k, v in calls[:12]]
    if result.get("notes"):
        _say("notes", result["notes"])
    for p in problems:
        _say("problem", {"what": p})

    if ctx.trace:
        metrics = read_per_layer(cell, facts)
    else:
        values = dict(result["end_to_end"], setup_s=ctx.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in cell["end_to_end"]}

    device = dict(ctx.device,
                  memory_peak_bytes=facts["memory_peak_bytes"])
    line = {"correct": bool(not problems and not ctx.rehearsal),
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": metrics, "device": device}
    trace = facts.get("trace")
    if ctx.trace and trace:
        from benchmarks import trace_reduce

        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        line["breakdown"] = trace_reduce.breakdown(trace)
    elif ctx.trace and not ctx.rehearsal:
        raise SystemExit("benchmark: the traced run holds no device "
                         "operation")
    if ctx.rehearsal:
        _say("rehearsal", {"note": "CPU rehearsal at tiny sizes: not a "
                                   "measurement, cannot be correct"})
    print(json.dumps(line), flush=True)
    return 3 if ctx.rehearsal else 0
