"""The program's spans and counters: one process-global API, on the
profiler's clock.

    from flexflow_tpu import obs

    with obs.span("ff:serve.step", step=3, active=2):
        ...
    obs.count("serve.host_bytes", nbytes)
    obs.snapshot()          # the aggregate; obs.reset() clears it

A span is named ``ff:<layer>.<what>``.  It records its name, start, end,
the enclosing span of the same thread and its ``args`` into a bounded
in-memory aggregate (per name: a count, total seconds, self seconds, and
the last ``RECORDS_PER_NAME`` raw records), and opens a
``jax.profiler.TraceAnnotation(name, **args)`` over the same interval:
under a profiler session the span lies in the ``.xplane.pb`` on the
device events' clock with its ``args`` as the event's stats, and with no
session the annotation is one dormant TraceMe.  There is no switch: a
span costs two ``perf_counter`` calls, that TraceMe and a dict update.

Importing this module registers, once, ``jax.monitoring`` listeners that
add JAX's own tracing, lowering and compilation seconds and the compile
cache's events to the ``compile.*`` counters, and one ``gc.callbacks``
entry that times the collector (``runtime.gc_s``,
``runtime.gc_collections.gen<g>``, and a ``ff:runtime.gc`` span for a
collection of the oldest generation).  ``flexflow_tpu.obs`` re-exports
the API lazily, so the jax-free report tools that import ``obs`` for the
JSONL readers still never import JAX.

A ``compile.<stage>_s`` counter sums JAX's duration event at every level
of nested ``jit``s, so it can exceed the wall clock; beside each stands
``compile.<stage>_wall_s``, the measure of the union of the same events'
intervals: the wall seconds in which that stage ran, no level twice.
"""

from __future__ import annotations

import collections
import gc
import threading
import time
import weakref
from typing import Dict

import jax

# raw records kept per span name; counter history entries kept per counter
RECORDS_PER_NAME = 256
HISTORY_PER_COUNTER = 512
# a counter's history holds its cumulative value once per this many
# seconds in which it moved: enough to ask "what did it read at time t"
_HISTORY_BUCKET_S = 0.25

_COMPILE_SECONDS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower_s",
    "/jax/core/compile/backend_compile_duration": "compile.backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "compile.cache_fetch_s",
}
# the oldest disjoint intervals of a stage are folded into its sum once
# this many are held (an enclosing event that came later could no longer
# swallow them: it is clipped to where the folded ones end)
_WALL_INTERVALS = 8192
_COMPILE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}

_lock = threading.Lock()
_tls = threading.local()
_spans: Dict[str, list] = {}          # name -> [count, total_s, self_s]
_records: Dict[str, collections.deque] = {}
_counters: Dict[str, float] = {}
_history: Dict[str, collections.deque] = {}   # name -> [t_first, t_last, v]
_programs: Dict[str, weakref.ref] = {}
# wall counter -> [measure, end of the folded intervals, [(a, b), ...]]
_walls: Dict[str, list] = {}
# collections timed but not yet published: (start, end, generation,
# collected, the thread, the span it ran inside).  See ``_on_gc``.
_gc_done: list = []
_gc_open: list = []                   # [start, annotation or None]
_OLDEST = len(gc.get_threshold()) - 1


class span:
    """Context manager; see the module docstring.  ``seconds`` holds the
    duration once the block has ended and ``self_s`` the part of it no
    span opened inside took.  ``args`` may be added to inside the block
    (a byte count known only at the end): late keys reach the in-memory
    record, not the profiler's event, whose stats are fixed when it
    opens."""

    __slots__ = ("name", "args", "start", "seconds", "self_s",
                 "_children_s", "_parent", "_ann")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args
        self.seconds = self.self_s = 0.0
        self._children_s = 0.0

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self._parent = stack[-1] if stack else None
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        self._ann.__exit__(*exc)
        _tls.stack.pop()
        self.seconds = dur = end - self.start
        parent = self._parent
        if parent is not None:
            parent._children_s += dur
        self.self_s = own = max(dur - self._children_s, 0.0)
        rec = {"name": self.name, "start": self.start, "end": end,
               "parent": parent.name if parent is not None else None,
               "self_s": own, "thread": threading.get_ident(),
               "args": self.args}
        with _lock:
            _keep(rec)
        return False


def _keep(rec: Dict) -> None:
    """One ended span into the aggregate; ``_lock`` held."""
    name = rec["name"]
    agg = _spans.get(name)
    if agg is None:
        agg = _spans[name] = [0, 0.0, 0.0]
        _records[name] = collections.deque(maxlen=RECORDS_PER_NAME)
    agg[0] += 1
    agg[1] += rec["end"] - rec["start"]
    agg[2] += rec["self_s"]
    _records[name].append(rec)


def _count(name: str, value: float, level: bool, now: float) -> None:
    """``count`` as of ``now``; ``_lock`` held."""
    v = _counters[name] = value if level \
        else _counters.get(name, 0) + value
    hist = _history.get(name)
    if hist is None:
        hist = _history[name] = collections.deque(
            maxlen=HISTORY_PER_COUNTER)
    if hist and now - hist[-1][0] < _HISTORY_BUCKET_S:
        hist[-1][1], hist[-1][2] = now, v
    else:
        hist.append([now, now, v])


def count(name: str, value: float = 1, *, level: bool = False) -> None:
    """Add ``value`` to the process-global counter ``name``; with
    ``level`` the counter is set to ``value`` instead (a size, a ratio:
    what reads wrong summed over calls)."""
    now = time.perf_counter()
    with _lock:
        _count(name, value, level, now)


def counter_at(snap: Dict, name: str, t: float) -> float:
    """What counter ``name`` of ``snap`` read at ``perf_counter`` time
    ``t``: its value after the last recorded moment at or before ``t``
    (0 before the first)."""
    value = 0.0
    for _t_first, t_last, v in snap["counter_history"].get(name, ()):
        if t_last > t:
            break
        value = v
    return value


def _aggregate() -> Dict:
    _publish_gc()
    return {"spans": {k: {"count": c, "total_s": t, "self_s": s}
                      for k, (c, t, s) in _spans.items()},
            "counters": dict(_counters)}


def snapshot() -> Dict:
    """A copy of the aggregate: ``spans`` (per name ``count``,
    ``total_s``, ``self_s``), ``counters``, ``records`` (the kept raw
    span records, by start), ``dropped`` (per name, how many records the
    bounded buffer has let go: a reader that needs every record of an
    interval must find none) and ``counter_history``."""
    with _lock:
        return dict(
            _aggregate(),
            records=sorted((dict(r) for d in _records.values() for r in d),
                           key=lambda r: r["start"]),
            dropped={k: _spans[k][0] - len(d) for k, d in _records.items()
                     if _spans[k][0] > len(d)},
            counter_history={k: [tuple(h) for h in d]
                             for k, d in _history.items()})


def summary() -> Dict:
    """The aggregate without its raw records: the body of the one
    ``spans`` record a surface writes to a live RunLog when it
    finishes."""
    with _lock:
        return _aggregate()


def reset() -> None:
    with _lock:
        del _gc_done[:]
        _spans.clear()
        _records.clear()
        _counters.clear()
        _history.clear()
        _walls.clear()


def note_program(name: str, model) -> None:
    """Remember (weakly) which model built the program ``name``, so that
    a trace reader can ask that model which operator each compiled
    instruction belongs to."""
    _programs[name] = weakref.ref(model)


def program(name: str):
    """The model noted under ``name``; None once it has been collected
    or when none was noted."""
    ref = _programs.get(name)
    return ref() if ref is not None else None


def _wall(name: str, start: float, end: float) -> float:
    """Add ``[start, end]`` to the union kept under ``name`` and return
    the union's measure; ``_lock`` held.  JAX reports a stage when it
    ends, so events come in the order they end and an enclosing one
    after those it holds: a new interval can only reach back over the
    newest ones kept."""
    w = _walls.get(name)
    if w is None:
        w = _walls[name] = [0.0, float("-inf"), []]
    start = max(start, w[1])
    kept = w[2]
    while kept and kept[-1][1] >= start:
        a, b = kept.pop()
        w[0] -= b - a
        start, end = min(start, a), max(end, b)
    if end > start:
        kept.append((start, end))
        w[0] += end - start
    if len(kept) > _WALL_INTERVALS:
        w[1] = kept[_WALL_INTERVALS // 2 - 1][1]
        del kept[:_WALL_INTERVALS // 2]
    return w[0]


def _on_duration(event: str, secs: float, **kw) -> None:
    name = _COMPILE_SECONDS.get(event)
    if name is not None:
        now = time.perf_counter()
        wall = name[:-2] + "_wall_s"
        with _lock:
            _count(name, secs, False, now)
            _count(wall, _wall(wall, now - secs, now), True, now)


def _on_event(event: str, **kw) -> None:
    name = _COMPILE_EVENTS.get(event)
    if name is not None:
        count(name)


def _on_gc(phase: str, info: Dict) -> None:
    """Time one collection.  The interpreter runs a collection between
    two bytecodes of whichever thread is due, possibly one that holds
    ``_lock`` (any statement above that makes a container can be the
    one): the callback therefore never waits for the lock.  It notes the
    collection in ``_gc_done`` and publishes what is noted only if the
    lock is free; otherwise the next collection, ``snapshot`` or
    ``summary`` does.  Collections do not nest, so ``_gc_open`` holds at
    most one."""
    gen = info["generation"]
    if phase == "start":
        ann = None
        if gen == _OLDEST:
            ann = jax.profiler.TraceAnnotation("ff:runtime.gc",
                                               generation=gen)
            ann.__enter__()
        _gc_open[:] = [time.perf_counter(), ann]
        return
    end = time.perf_counter()
    if not _gc_open:
        return              # registered while a collection was running
    start, ann = _gc_open
    del _gc_open[:]
    if ann is not None:
        ann.__exit__(None, None, None)
    stack = getattr(_tls, "stack", None)
    _gc_done.append((start, end, gen, info["collected"],
                     threading.get_ident(),
                     stack[-1].name if stack else None))
    if _lock.acquire(blocking=False):
        try:
            _publish_gc()
        finally:
            _lock.release()


def _publish_gc() -> None:
    """The collections noted so far into the counters (as of their own
    end) and, for the oldest generation, the records; ``_lock`` held."""
    while _gc_done:
        start, end, gen, collected, thread, parent = _gc_done.pop(0)
        _count("runtime.gc_s", end - start, False, end)
        _count(f"runtime.gc_collections.gen{gen}", 1, False, end)
        if gen == _OLDEST:
            _keep({"name": "ff:runtime.gc", "start": start, "end": end,
                   "parent": parent, "self_s": end - start,
                   "thread": thread,
                   "args": {"generation": gen, "collected": collected}})


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
gc.callbacks.append(_on_gc)
