"""From a profiler trace to the numbers the per-layer metrics read.

    events = load_xplane(path)          # needs jax; everything else is pure
    red = reduce_events(events)

An event is ``{"plane", "line", "name", "start_ns", "dur_ns"}``, which is
what ``jax.profiler.ProfileData`` gives for an ``.xplane.pb`` (looked at
by hand on this installation, PR 24: device planes are ``/device:TPU:<n>``
with the lines ``XLA Ops``, ``Async XLA Ops`` and ``XLA Modules``; an op's
name is its whole HLO instruction, ``%fusion.2 = bf16[..] fusion(..),
kind=kOutput, ..``; ``jax.profiler.TraceAnnotation`` spans land on the
``python`` line of ``/host:CPU``, on the device events' clock to about a
millisecond).

The reduction (copied in idea from ``flexflow_tpu/utils/hlo_profile.py``'s
per-op attribution, which stays with the program and may change; this
copy may not):

  busy        union of the intervals in which an operation ran on a
              device (``XLA Ops`` and ``Async XLA Ops``), per device, mean
              of devices
  window      the ``bench:trace_window`` span, else first to last device
              event
  idle gaps   the window minus busy, each gap of 10 us or more charged to
              the innermost ``bench:`` host span that covers it, shorter
              ones summed as ``between_operations_under_10us``
  op seconds  self time per instruction (an op that contains others, a
              ``while``, is charged only what its children do not cover),
              mean of devices
  collectives union of the collective instructions' intervals (sync, and
              start-to-done of async ones) and the part of it during
              which no other instruction ran on that device
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:trace_window"
SHORT_GAP_NS = 10_000
SHORT_GAPS = "between_operations_under_10us"
OUTSIDE_SPANS = "outside_bench_spans"

COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                      "all-to-all", "collective-permute",
                      "collective-broadcast")

_NAME_RE = re.compile(r"^%?([\w.\-]+) = ")
_OPCODE_RE = re.compile(r"(?<=\s)([a-z][\w\-]*)\(")

Interval = Tuple[float, float]


def load_xplane(path: str) -> List[Dict]:
    """Every event of the device planes, and the ``bench:`` spans of the
    host planes, of one ``.xplane.pb``."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, ASYNC_LINE):
                continue
            for e in line.events:
                if device or e.name.startswith(SPAN_PREFIX):
                    out.append({"plane": plane.name, "line": line.name,
                                "name": e.name,
                                "start_ns": float(e.start_ns),
                                "dur_ns": float(e.duration_ns)})
    return out


def split_name(name: str) -> Tuple[str, str]:
    """(short name, opcode) of a device event: ``%fusion.2 = .. fusion(..)``
    gives ``("fusion.2", "fusion")``; a bare ``all-reduce.1`` gives
    ``("all-reduce.1", "all-reduce")``."""
    m = _NAME_RE.match(name)
    if m:
        op = _OPCODE_RE.search(name, m.end())
        return m.group(1), (op.group(1) if op else "")
    return name, re.sub(r"[.\d]+$", "", name)


def is_collective(opcode: str) -> bool:
    return any(opcode == c or opcode.startswith(c + "-")
               for c in COLLECTIVE_OPCODES)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted disjoint intervals covering the same points."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def measure(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """``a`` minus ``b``; both sorted and disjoint."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def self_times(events: List[Dict]) -> List[Tuple[Dict, float]]:
    """(event, self nanoseconds) for the events of ONE line, where an
    event that lies inside another is the other's child."""
    order = sorted(events, key=lambda e: (e["start_ns"], -e["dur_ns"]))
    selfs = [e["dur_ns"] for e in order]
    stack: List[int] = []
    for i, e in enumerate(order):
        end = e["start_ns"] + e["dur_ns"]
        while stack and (order[stack[-1]]["start_ns"]
                         + order[stack[-1]]["dur_ns"]) < end:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= e["dur_ns"]
        stack.append(i)
    return [(e, max(s, 0.0)) for e, s in zip(order, selfs)]


def flatten_spans(spans: List[Dict]) -> List[Tuple[float, float, str]]:
    """Disjoint (start, end, name) segments, sorted, in which the
    innermost (latest started) covering span names the segment."""
    points = sorted({p for s in spans
                     for p in (s["start_ns"], s["start_ns"] + s["dur_ns"])})
    order = sorted(spans, key=lambda s: s["start_ns"])
    out: List[Tuple[float, float, str]] = []
    live: List[Dict] = []
    nxt = 0
    for lo, hi in zip(points, points[1:]):
        while nxt < len(order) and order[nxt]["start_ns"] <= lo:
            live.append(order[nxt])
            nxt += 1
        live = [s for s in live if s["start_ns"] + s["dur_ns"] > lo]
        if live:
            name = max(live, key=lambda s: s["start_ns"])["name"]
            if out and out[-1][2] == name and out[-1][1] == lo:
                out[-1] = (out[-1][0], hi, name)
            else:
                out.append((lo, hi, name))
    return out


def charge_gaps(gaps: List[Interval],
                segments: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Nanoseconds of ``gaps`` by the span segment that covers them."""
    starts = [s[0] for s in segments]
    out: Dict[str, float] = defaultdict(float)
    for lo, hi in gaps:
        if hi - lo < SHORT_GAP_NS:
            out[SHORT_GAPS] += hi - lo
            continue
        covered = 0.0
        i = max(bisect.bisect_right(starts, lo) - 1, 0)
        while i < len(segments) and segments[i][0] < hi:
            a, b = max(segments[i][0], lo), min(segments[i][1], hi)
            if b > a:
                out[segments[i][2]] += b - a
                covered += b - a
            i += 1
        if hi - lo - covered > 0:
            out[OUTSIDE_SPANS] += hi - lo - covered
    return dict(out)


def reduce_events(events: List[Dict],
                  window: Optional[Interval] = None) -> Optional[Dict]:
    """The reduction described at the top; None for a trace in which no
    operation ran on a device.  Seconds throughout."""
    by_device: Dict[str, List[Dict]] = defaultdict(list)
    spans = []
    for e in events:
        if e["plane"].startswith("/device:"):
            if e["line"] in (OPS_LINE, ASYNC_LINE):
                by_device[e["plane"]].append(e)
        elif e["name"].startswith(SPAN_PREFIX):
            spans.append(e)
    if not by_device:
        return None
    if window is None:
        w = [s for s in spans if s["name"] == WINDOW_SPAN]
        if w:
            window = (w[0]["start_ns"], w[0]["start_ns"] + w[0]["dur_ns"])
        else:
            all_ev = [e for evs in by_device.values() for e in evs]
            window = (min(e["start_ns"] for e in all_ev),
                      max(e["start_ns"] + e["dur_ns"] for e in all_ev))
    w0, w1 = window
    segments = flatten_spans([s for s in spans if s["name"] != WINDOW_SPAN])
    n = len(by_device)
    busy = coll = exposed = 0.0
    gaps_by: Dict[str, float] = defaultdict(float)
    op_ns: Dict[str, float] = defaultdict(float)
    for evs in by_device.values():
        evs = [e for e in evs
               if e["start_ns"] < w1 and e["start_ns"] + e["dur_ns"] > w0]
        ivs = lambda sel: union(clip(((e["start_ns"],
                                       e["start_ns"] + e["dur_ns"])
                                      for e in sel), w0, w1))
        kinds = {id(e): split_name(e["name"]) for e in evs}
        busy_iv = ivs(evs)
        coll_iv = ivs(e for e in evs if is_collective(kinds[id(e)][1]))
        work_iv = ivs(e for e in evs if e["line"] == OPS_LINE
                      and not is_collective(kinds[id(e)][1]))
        busy += measure(busy_iv)
        coll += measure(coll_iv)
        exposed += measure(subtract(coll_iv, work_iv))
        for name, ns in charge_gaps(subtract([(w0, w1)], busy_iv),
                                    segments).items():
            gaps_by[name] += ns
        for e, ns in self_times([e for e in evs if e["line"] == OPS_LINE]):
            short, opcode = kinds[id(e)]
            op_ns[f"{short}|{opcode}"] += ns
    sec = 1e-9 / n
    return {
        "devices": n,
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy * sec,
        "collective_s": coll * sec,
        "exposed_collective_s": exposed * sec,
        "idle_gaps_s": {k: v * sec for k, v in gaps_by.items()},
        "op_s": {k: v * sec for k, v in op_ns.items()},
    }


def kernel_seconds(reduction: Dict, kernel: str) -> float:
    """Device seconds (self time, mean of devices) of the instructions
    named for the kernels ``kernel*``, e.g. ``ff_flash_``: the name a
    ``pallas_call`` was given, with what autodiff puts before it
    (``ff_flash_fwd.3``, ``jvp_ff_ce_fwd_.1``,
    ``transpose_jvp_ff_ce_bwd_dw__``; seen in the step compiled for a
    described v5e chip, PR 24)."""
    named = re.compile(r"(?:^|_)" + re.escape(kernel))
    return sum(s for k, s in reduction["op_s"].items()
               if named.search(k.split("|")[0]))


def breakdown(reduction: Dict, top: int = 10) -> Dict:
    """The contract's ``breakdown``: the ``top`` device operations by
    time and the idle gaps by what the host was doing."""
    rank = lambda d: [[k.replace("|", "__"), v] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(reduction["op_s"]),
            "idle_gaps": rank(reduction["idle_gaps_s"])}
