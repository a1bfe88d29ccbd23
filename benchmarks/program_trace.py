"""What the program's own spans, counters and operator names (PR 26) add
to a traced run: device time by FlexFlow operator and pass, collectives
by operator, idle gaps charged to the innermost of the ``bench:`` and
``ff:`` spans, the ``ff:`` spans and ``compile.*`` counters themselves,
and an estimate of how far the device clock leads the host's.

    prog = program_facts(facts)     # cached in facts; None: nothing to read

It loads the run's newest ``.xplane.pb`` a second time (``trace_reduce``
keeps neither the ``ff:`` spans nor an event's line once reduced) and
reuses ``trace_reduce``'s interval arithmetic by import.  Where each
instruction belongs comes from the program: ``FFModel._apply`` runs every
operator under ``jax.named_scope``, ``flexflow_tpu/obs/optrace.py`` reads
the names back from the compiled step, and the model that built the step
is asked for that table (``obs.program("train_step").operator_table()``).
A device event carries no ``op_name`` (looked at on the v5e, PR 26: the
name is the HLO text, the stats are offsets and durations), so the two
are joined by instruction name, and the join is refused unless EVERY
instruction of the traced window is in the table: a step compiled
differently must not be read through another step's names.

On a program without ``flexflow_tpu/obs/spans.py`` (the parent of
PR 26) everything here returns None and the readers leave their metrics
out.  The first call prints two lines, ``benchmark: operators`` and
``benchmark: program_spans``, before the result line.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from benchmarks import trace_reduce as tr

MODULES_LINE = "XLA Modules"
FENCE_SPAN = "bench:fence"
STEP_SPAN = "bench:train_step"
PROGRAM_PREFIX = "ff:"
PASSES = ("forward", "backward", "update", "regrid", "other")
ENTRY_SPANS = ("ff:entry.abstract_state", "ff:entry.init",
               "ff:entry.opt_state", "ff:entry.graph_plan",
               "ff:entry.regrid_plan")
_KEY = "program_trace"


def newest_xplane(facts: Dict) -> Optional[str]:
    """The trace the harness just wrote for this cell (its scratch
    directory is named by the cell, and a cell's name is
    ``<config>.<traffic>``)."""
    from benchmarks import harness

    cell = f"{facts['config']['name']}.{facts['mix']['name']}"
    files = glob.glob(os.path.join(harness.CHECKOUT, ".bench_cache",
                                   "scratch", cell, "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load_xplane(path: str) -> List[Dict]:
    """Events as ``trace_reduce.load_xplane`` gives them, with three
    additions: the ``XLA Modules`` line of each device, the ``ff:`` host
    spans beside the ``bench:`` ones, and each span's stats as ``args``.
    Off the chip (a CPU rehearsal has no ``/device:`` plane) the host
    events that carry an ``hlo_op`` stat stand in for the device's, one
    line a thread: enough to rehearse the join, never a measurement."""
    import jax

    planes = list(jax.profiler.ProfileData.from_file(path).planes)
    on_chip = any(p.name.startswith("/device:") for p in planes)
    out = []
    for plane in planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (tr.OPS_LINE, tr.ASYNC_LINE,
                                            MODULES_LINE):
                continue
            for e in line.events:
                ev = {"plane": plane.name, "line": line.name,
                      "name": e.name, "start_ns": float(e.start_ns),
                      "dur_ns": float(e.duration_ns)}
                if device:
                    out.append(ev)
                elif e.name.startswith((tr.SPAN_PREFIX, PROGRAM_PREFIX)):
                    ev["args"] = {k: v for k, v in e.stats}
                    out.append(ev)
                elif not on_chip and any(k == "hlo_op" for k, _ in e.stats):
                    ev.update(plane="/device:rehearsal", thread=line.name,
                              line=tr.OPS_LINE)
                    out.append(ev)
    return out


def clock_lead_ns(events: List[Dict]) -> Optional[Tuple[float, float]]:
    """(least, most) nanoseconds the device clock can lead the host's;
    both negative where it lags.  The k-th ``XLA Modules`` event of a
    device is the program the k-th ``bench:train_step`` dispatched (the
    traced window opens on an idle device).  Most: no program starts
    before its dispatch; after a fence the device is idle, so what is
    left is the time a launch takes.  Least: a ``bench:fence`` returns
    only after the last program dispatched before it has ended; what is
    left is the time the host took to wake."""
    fences = [e for e in events if e["name"] == FENCE_SPAN]
    steps = sorted(e["start_ns"] for e in events
                   if e["name"] == STEP_SPAN)
    by_device: Dict[str, List[Dict]] = defaultdict(list)
    for e in events:
        if e["line"] == MODULES_LINE:
            by_device[e["plane"]].append(e)
    least, most = [], []
    for modules in by_device.values():
        modules.sort(key=lambda e: e["start_ns"])
        most += [m["start_ns"] - t for m, t in zip(modules, steps)]
        for f in fences:
            dispatched = bisect.bisect_right(steps, f["start_ns"])
            if 0 < dispatched <= len(modules):
                last = modules[dispatched - 1]
                least.append(last["start_ns"] + last["dur_ns"]
                             - f["start_ns"] - f["dur_ns"])
    if not least or not most:
        return None
    return max(least), min(most)


def reduce_program(events: List[Dict],
                   table: Optional[Dict[str, Tuple[str, str]]],
                   say=print) -> Optional[Dict]:
    """Seconds (mean of devices, over the traced window) by operator and
    pass, collectives by operator and idle gaps by the innermost
    ``bench:`` or ``ff:`` span.  ``table`` is
    ``{instruction: (operator, pass)}``; with None, or with a table that
    lacks an instruction the window ran, the operator part is refused
    (``say`` is told why) and only the gaps and the clock lead come
    back."""
    by_device: Dict[str, List[Dict]] = defaultdict(list)
    spans = []
    for e in events:
        if e["plane"].startswith("/device:"):
            if e["line"] in (tr.OPS_LINE, tr.ASYNC_LINE):
                by_device[e["plane"]].append(e)
        elif e["name"].startswith((tr.SPAN_PREFIX, PROGRAM_PREFIX)):
            spans.append(e)
    if not by_device:
        return None
    w = [s for s in spans if s["name"] == tr.WINDOW_SPAN]
    if w:
        w0, w1 = w[0]["start_ns"], w[0]["start_ns"] + w[0]["dur_ns"]
    else:
        all_ev = [e for evs in by_device.values() for e in evs]
        w0 = min(e["start_ns"] for e in all_ev)
        w1 = max(e["start_ns"] + e["dur_ns"] for e in all_ev)
    segments = tr.flatten_spans([s for s in spans
                                 if s["name"] != tr.WINDOW_SPAN])
    n = len(by_device)
    busy = ops_busy = 0.0
    gaps: Dict[str, float] = defaultdict(float)
    op_ns: Dict[Tuple[str, str, str], float] = defaultdict(float)
    coll_ns: Dict[str, float] = defaultdict(float)
    unknown = set()
    for evs in by_device.values():
        evs = [e for e in evs
               if e["start_ns"] < w1 and e["start_ns"] + e["dur_ns"] > w0]
        iv = lambda sel: tr.union(tr.clip(
            ((e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in sel),
            w0, w1))
        busy_iv = iv(evs)
        busy += tr.measure(busy_iv)
        for name, ns in tr.charge_gaps(tr.subtract([(w0, w1)], busy_iv),
                                       segments).items():
            gaps[name] += ns
        kinds = {id(e): tr.split_name(e["name"]) for e in evs}
        ops = [e for e in evs if e["line"] == tr.OPS_LINE]
        ops_busy += tr.measure(iv(ops))
        threads = defaultdict(list)     # one line a device on the chip
        for e in ops:
            threads[e.get("thread")].append(e)
        for line in threads.values():
            for e, ns in tr.self_times(line):
                short = kinds[id(e)][0]
                if table is not None and short not in table:
                    unknown.add(short)
                    continue
                operator, pas = (table or {}).get(short, ("", "other"))
                op_ns[(operator, pas, short)] += ns
        coll_iv: Dict[str, List] = defaultdict(list)
        for e in evs:               # sync ones, and start-to-done of async
            short, opcode = kinds[id(e)]
            if tr.is_collective(opcode):
                operator = (table or {}).get(short, ("", ""))[0]
                coll_iv[operator or "(no operator)"].append(
                    (e["start_ns"], e["start_ns"] + e["dur_ns"]))
        for operator, ivs in coll_iv.items():
            coll_ns[operator] += tr.measure(tr.union(tr.clip(ivs, w0, w1)))
    sec = 1e-9 / n
    lead = clock_lead_ns(events)
    out = {"devices": n, "window_s": (w1 - w0) * 1e-9,
           "busy_s": busy * sec, "ops_busy_s": ops_busy * sec,
           "idle_gaps_s": {k: v * sec for k, v in gaps.items()},
           "clock_lead_ms": None if lead is None
           else [lead[0] * 1e-6, lead[1] * 1e-6]}
    if table is None:
        return out
    if unknown:
        say(f"benchmark: program_trace: refused: {len(unknown)} "
            f"instruction(s) of the traced window are not in the model's "
            f"operator table (e.g. {sorted(unknown)[:3]}): the step that "
            f"ran is not the step the table was compiled from")
        return out
    by_pass: Dict[str, float] = dict.fromkeys(PASSES, 0.0)
    by_op: Dict[Tuple[str, str], float] = defaultdict(float)
    instr: Dict[str, Dict] = {}
    for (operator, pas, short), ns in op_ns.items():
        by_pass[pas] += ns * sec
        by_op[(operator, pas)] += ns * sec
        instr[short] = {"operator": operator, "pass": pas, "s": ns * sec}
    out.update(pass_s=by_pass, operator_s=dict(by_op), instruction_s=instr,
               collective_s={k: v * sec for k, v in coll_ns.items()})
    return out


def _operator_table(say) -> Optional[Dict]:
    """The table of the train step the program noted, from the model
    that built it; None (and why) where there is none."""
    try:
        from flexflow_tpu import obs

        model = obs.program("train_step")
    except (ImportError, AttributeError):
        return None           # a program from before PR 26: no names
    if model is None:
        say("benchmark: program_trace: no live model has noted a train "
            "step")
        return None
    try:
        return model.operator_table()
    except Exception as e:    # a reader must not fail the run
        say(f"benchmark: program_trace: operator_table() failed: "
            f"{type(e).__name__}: {e}")
        return None


def program_spans(facts: Dict) -> Optional[Dict]:
    """The program's span and counter aggregate, with what the entry
    points spent before the window opened; None on a program without
    ``obs.snapshot``."""
    try:
        from flexflow_tpu import obs

        snap = obs.snapshot()
        counter_at = obs.counter_at
    except (ImportError, AttributeError):
        return None
    t_open = facts["fences"][0][0]
    before = [r for r in snap["records"] if r["end"] <= t_open]
    return {
        "spans": snap["spans"], "counters": snap["counters"],
        "entry_before_open_s": {
            name: sum(r["self_s"] for r in before if r["name"] == name)
            for name in ENTRY_SPANS},
        "compile_before_open_s": {
            name: counter_at(snap, name, t_open)
            for name in ("compile.trace_s", "compile.lower_s",
                         "compile.backend_s", "compile.cache_fetch_s")},
    }


def _top(d: Dict, n: int, scale: float) -> List:
    return [[k, round(v * scale, 4)] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def program_facts(facts: Dict, say=print) -> Optional[Dict]:
    """Everything above for this run, computed once and kept in
    ``facts``; None where there is nothing to read (no fences: not a
    run; no ``obs.snapshot``: a program from before PR 26)."""
    if _KEY in facts:
        return facts[_KEY]
    facts[_KEY] = None
    if not facts.get("fences"):
        return None
    prog = program_spans(facts)
    if prog is None:
        return None
    steps = facts.get("traced_steps") or 0
    path = newest_xplane(facts) if steps else None
    red = None
    if path:
        events = load_xplane(path)
        red = reduce_program(events, _operator_table(say), say)
    prog["trace"] = red
    prog["on_chip"] = bool(facts.get("trace"))
    prog["steps"] = steps
    facts[_KEY] = prog

    ms = 1e3 / steps if steps else 0.0
    if red and "pass_s" in red:
        named = sum(v for k, v in red["pass_s"].items() if k != "other")
        say("benchmark: operators " + json.dumps({
            "on_chip": prog["on_chip"], "traced_steps": steps,
            "ms_per_step": {k: round(v * ms, 4)
                            for k, v in red["pass_s"].items()},
            "busy_ms_per_step": round(red["busy_s"] * ms, 4),
            "ops_busy_ms_per_step": round(red["ops_busy_s"] * ms, 4),
            "attributed_share": round(
                named / sum(red["pass_s"].values()), 5) if named else None,
            "top": [[f"{o or '(none)'}|{p}", v] for (o, p), v in
                    ((k, round(v * ms, 4)) for k, v in sorted(
                        red["operator_s"].items(),
                        key=lambda kv: -kv[1])[:15])],
            "collectives_ms_per_step": _top(red["collective_s"], 15, ms),
            "instructions": [[k, v["operator"] or "(none)", v["pass"],
                              round(v["s"] * ms, 4)] for k, v in
                             sorted(red["instruction_s"].items(),
                                    key=lambda kv: -kv[1]["s"])[:15]]}))
    else:
        say("benchmark: operators " + json.dumps(
            {"on_chip": prog["on_chip"], "traced_steps": steps,
             "refused": True}))
    say("benchmark: program_spans " + json.dumps({
        "spans": {k: [v["count"], round(v["total_s"], 6),
                      round(v["self_s"], 6)]
                  for k, v in sorted(prog["spans"].items())},
        "counters": {k: round(v, 6)
                     for k, v in sorted(prog["counters"].items())},
        "entry_before_open_s": {k: round(v, 6) for k, v in
                                prog["entry_before_open_s"].items()},
        "compile_before_open_s": {k: round(v, 6) for k, v in
                                  prog["compile_before_open_s"].items()},
        "idle_gaps_ms_per_step": _top(red["idle_gaps_s"], 10, ms)
        if red else [],
        "clock_lead_ms": red["clock_lead_ms"] if red else None}))
    return prog


def pass_ms_per_step(facts: Dict, pas: str) -> Optional[float]:
    """Device milliseconds a traced step spends in one pass; None off
    the chip and where the operator table was refused."""
    prog = program_facts(facts)
    if not prog or not prog["on_chip"] or not prog["steps"]:
        return None
    red = prog["trace"]
    if not red or "pass_s" not in red:
        return None
    return 1e3 * red["pass_s"][pas] / prog["steps"]
