"""The host's side of a run, from the program's own spans and counters
(PR 36): what the step's first call spent its wall seconds on, and what
the host did inside the measured window.

    host = host_facts(facts)    # cached in facts; None: nothing to read

The program records ``ff:entry.step_build`` around the first call of the
train step (its late ``args``: the wall seconds JAX traced, lowered,
compiled or fetched inside it), ``ff:entry.trace_op`` and
``ff:entry.trace_block`` around each operator and recomputed block while
JAX traces them (summed in ``entry.trace_op_s.<class>`` and
``entry.trace_block_s.<block>``), ``kernels.traced.<kernel>`` once a
trace of a kernel's body, ``ff:runtime.step`` around every later call,
and times the collector (``runtime.gc_s``, ``ff:runtime.gc`` for the
oldest generation).  ``compile.<stage>_wall_s`` is the union of JAX's
duration events of a stage, where ``compile.<stage>_s`` sums every
nested level.

Two clocks meet here.  ``facts["fences"]`` lie on a clock with the
profiler's stop cut out (``harness.Window.paused_s``); the program's
records and the harness's ``bench:`` records lie on ``perf_counter``
itself.  The window is therefore rebuilt from the ``bench:`` records:
fence interval ``k`` runs from the start of its first
``bench:train_step`` to the end of its ``bench:fence``, the window's
seconds are the intervals' sum, and what lies between two intervals (the
harness's own bookkeeping, and once the profiler's stop) is on neither
side of a share.

A reader that needs every record of the window and finds that the
program's bounded buffer (256 a name) has let some of them go gets None,
never a share of part of a window; the line says which.  On a program
from before PR 36 every reader gets None.  The first call prints one
line, ``benchmark: host_timeline {...}``, before the result line.
"""

from __future__ import annotations

import bisect
import json
import re
from typing import Dict, List, Optional, Tuple

from benchmarks import trace_reduce as tr
from benchmarks.stats import interval_step_seconds

BUILD_SPAN = "ff:entry.step_build"
STEP_SPAN = "ff:runtime.step"
GC_SPAN = "ff:runtime.gc"
STAGES = ("trace", "lower", "backend", "cache_fetch")
# with the harness's ``bench:fence``, the host events the window's five
# longest are taken from
PROGRAM_EVENTS = (STEP_SPAN, GC_SPAN, "ff:runtime.prefetch_wait",
                  "ff:runtime.prefetch_put")
# a kernel's ``kernels.traced.<name>`` beside the counter of traced calls
# of its family (one count a call site of the operator that runs it)
FAMILIES = (("ff_flash_win_", re.compile(r"^kernels\.flash\..*\.w\d+$")),
            ("ff_flash_", re.compile(r"^kernels\.flash\.(?!.*\.w\d+$)")),
            ("ff_ce_", re.compile(r"^kernels\.ce\.fwd\.")),
            ("ff_gmm", re.compile(r"^kernels\.gmm\.ff_gmm\.")),
            ("ff_ssd_", re.compile(r"^kernels\.ssd\.pallas\.")))
_KEY = "host_timeline"

Interval = Tuple[float, float]


def window_intervals(facts: Dict) -> List[Interval]:
    """The window's fence intervals on ``perf_counter`` (see the module
    docstring), from the harness's ``bench:`` records; [] where the run
    kept none."""
    spans = facts.get("spans") or {}
    steps = sorted(t0 for t0, _ in spans.get("bench:train_step", ()))
    out, after = [], float("-inf")
    for _, end in sorted(spans.get("bench:fence", ())):
        i = bisect.bisect_left(steps, after)
        if i < len(steps) and steps[i] < end:
            out.append((steps[i], end))
        after = end
    return out


def _snapshot() -> Optional[Dict]:
    try:
        from flexflow_tpu import obs

        return dict(obs.snapshot(), counter_at=obs.counter_at)
    except (ImportError, AttributeError):
        return None           # a program from before PR 26


def _inside(records: List[Dict], intervals: List[Interval]) -> float:
    """Seconds of ``records`` that lie inside ``intervals`` (records of
    one name on one thread do not overlap each other)."""
    return sum(tr.measure(tr.clip(intervals, r["start"], r["end"]))
               for r in records)


def _kept_since(snap: Dict, name: str, t: float) -> bool:
    """Does the snapshot hold every record of ``name`` that ended after
    ``t``?  The buffer keeps the newest: it does unless it has dropped
    some and its oldest starts after ``t``."""
    if not snap.get("dropped", {}).get(name):
        return True
    kept = [r["start"] for r in snap["records"] if r["name"] == name]
    return bool(kept) and min(kept) <= t


def _top(d: Dict[str, float], n: int = 10) -> List:
    return [[k, round(v, 4)] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def _first_call(snap: Dict, builds: List[Dict], t_open: float) -> Dict:
    """``step_build``, ``trace`` and ``kernels_traced_calls`` of the
    line: what the step's first call spent its seconds on, read at the
    window's open."""
    counters = snap["counters"]

    def at_open(prefix: str) -> Dict[str, float]:
        return {k[len(prefix):]: snap["counter_at"](snap, k, t_open)
                for k in counters if k.startswith(prefix)}

    out: Dict = {}
    if builds:
        total = sum(r["end"] - r["start"] for r in builds)
        parts = {s + "_s": sum(r["args"].get(s + "_s", 0.0) for r in builds)
                 for s in STAGES}
        # JAX's backend event runs around the cache's look-up: a fetch
        # lies inside it, and ``backend_s`` here is the compilation proper
        parts["backend_s"] = max(parts["backend_s"]
                                 - parts["cache_fetch_s"], 0.0)
        parts["rest_s"] = max(total - sum(parts.values()), 0.0)
        out["step_build"] = dict(
            {k: round(v, 4) for k, v in parts.items()}, s=round(total, 4),
            ops=builds[0]["args"].get("ops"),
            blocks=builds[0]["args"].get("blocks"),
            first_steps_s=round(t_open - builds[-1]["end"], 4))
        classes = at_open("entry.trace_op_s.")
        in_ops = sum(classes.values())
        out["trace"] = {
            "wall_s": round(parts["trace_s"], 4),
            "operators_self_s": round(in_ops, 4),
            "outside_operator_s": round(
                max(parts["trace_s"] - in_ops, 0.0), 4),
            "classes": _top(classes),
            "blocks": _top(at_open("entry.trace_block_s."))}
    calls = at_open("")
    out["kernels_traced_calls"] = []
    for name, traced in sorted(at_open("kernels.traced.").items()):
        family = next((rx for head, rx in FAMILIES
                       if name.startswith(head)), None)
        out["kernels_traced_calls"].append(
            [name, int(traced), int(sum(
                v for c, v in calls.items()
                if family is not None and family.search(c)))])
    return out


def _window(facts: Dict, by_name: Dict[str, List[Dict]],
            intervals: List[Interval], short: List[str]) -> Dict:
    """``window`` of the line: each fence interval's ms a step beside the
    longest host events (name, args, ms, the interval they began in)."""
    t_open, t_close = intervals[0][0], intervals[-1][1]
    ends = [b for _, b in intervals]
    events = [r for name in PROGRAM_EVENTS for r in by_name.get(name, ())]
    events += [{"name": "bench:fence", "start": a, "end": b, "args": {}}
               for a, b in facts["spans"]["bench:fence"]]
    events = sorted((e for e in events
                     if e["end"] > t_open and e["start"] < t_close),
                    key=lambda e: e["start"] - e["end"])
    shown = [[e["name"], e["args"], round(1e3 * (e["end"] - e["start"]), 3),
              min(bisect.bisect_left(ends, e["start"]), len(ends) - 1)]
             for e in events]
    longest: Dict[str, List] = {}
    for name, *rest in shown:
        longest.setdefault(name, rest)
    return {
        "s": round(tr.measure(intervals), 4),
        "between_intervals_s": round(
            t_close - t_open - tr.measure(intervals), 4),
        "steps": sum(1 for e in events if e["name"] == STEP_SPAN),
        "interval_step_ms": [round(1e3 * s, 3) for s in interval_step_seconds(
            facts["fences"], facts["items_per_step"])],
        "longest": shown[:5], "longest_by_name": longest,
        "records_short": short}


def host_facts(facts: Dict, say=print) -> Optional[Dict]:
    """The four metrics and the line's other parts for this run, computed
    once and kept in ``facts``; None where there is nothing to read (no
    fences: not a run; no ``obs.snapshot``: a program from before
    PR 26)."""
    if _KEY in facts:
        return facts[_KEY]
    facts[_KEY] = None
    intervals = window_intervals(facts)
    snap = _snapshot() if facts.get("fences") and intervals else None
    if snap is None:
        return None
    at = lambda name, t: snap["counter_at"](snap, name, t)
    t_open, t_close = intervals[0][0], intervals[-1][1]
    window_s = tr.measure(intervals)
    by_name: Dict[str, List[Dict]] = {}
    for r in snap["records"]:
        by_name.setdefault(r["name"], []).append(r)
    short = sorted(n for n in PROGRAM_EVENTS
                   if not _kept_since(snap, n, t_open))
    counters = snap["counters"]
    refused: Dict[str, str] = {}
    metrics: Dict[str, Optional[float]] = dict.fromkeys(
        ("entry.step_build_s", "entry.trace_wall_s",
         "runtime.host_dispatch_share", "runtime.gc_pause_share"))

    builds = [r for r in by_name.get(BUILD_SPAN, ()) if r["end"] <= t_open]
    if builds:
        metrics["entry.step_build_s"] = sum(r["end"] - r["start"]
                                            for r in builds)
    else:
        refused["entry.step_build_s"] = f"no {BUILD_SPAN} before the window"
    if "compile.trace_wall_s" in counters:
        metrics["entry.trace_wall_s"] = at("compile.trace_wall_s", t_open) \
            + at("compile.lower_wall_s", t_open)
    else:
        refused["entry.trace_wall_s"] = "no compile.trace_wall_s"
    if STEP_SPAN not in snap["spans"]:
        refused["runtime.host_dispatch_share"] = f"no {STEP_SPAN}"
    elif STEP_SPAN in short:
        refused["runtime.host_dispatch_share"] = (
            f"{snap['dropped'][STEP_SPAN]} {STEP_SPAN} records dropped, "
            f"some of them the window's")
    else:
        metrics["runtime.host_dispatch_share"] = 100.0 * _inside(
            by_name.get(STEP_SPAN, ()), intervals) / window_s
    history = snap["counter_history"].get("runtime.gc_s")
    if "runtime.gc_s" not in counters:
        refused["runtime.gc_pause_share"] = "no runtime.gc_s"
    elif not history or history[0][0] > t_open:
        refused["runtime.gc_pause_share"] = (
            "the history of runtime.gc_s no longer reaches the window's "
            "open")
    else:
        gaps = tr.subtract([(t_open, t_close)], intervals)
        gc_s = at("runtime.gc_s", t_close) - at("runtime.gc_s", t_open) \
            - sum(at("runtime.gc_s", b) - at("runtime.gc_s", a)
                  for a, b in gaps)
        metrics["runtime.gc_pause_share"] = 100.0 * max(gc_s, 0.0) / window_s

    line = _first_call(snap, builds, t_open)
    line["window"] = _window(facts, by_name, intervals, short)
    line["metrics"] = {k: None if v is None else round(v, 6)
                       for k, v in metrics.items()}
    if refused:
        line["refused"] = refused
    say("benchmark: host_timeline " + json.dumps(line, default=str))
    facts[_KEY] = {"metrics": metrics, "line": line}
    return facts[_KEY]


def metric(facts: Dict, name: str) -> Optional[float]:
    """One of the four metrics; None where its reader found nothing to
    read, or not all of what it needs."""
    host = host_facts(facts)
    return None if host is None else host["metrics"][name]
