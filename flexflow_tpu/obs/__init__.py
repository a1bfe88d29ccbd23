"""Unified run-telemetry subsystem: one structured, machine-readable event
stream for training, strategy search, and audit/bench.

The reference FlexFlow's only instruments are per-task cudaEvent prints and
Legion ``-lg:prof`` traces (SURVEY §5); this repo already measures more
(OpProfiler, XProf traces, rooflines, the compiled-HLO collective audit)
but each instrument spoke its own dialect — free-form ``fit()`` prints, a
single final dict from ``StrategySearch.search()``, a bench JSON line
fished out of mixed stdout.  This package gives them ONE record schema:

  * every record is one JSON object per line (JSONL), stamped with the
    run id and a host wall-clock timestamp:
    ``{"run": <id>, "ts": <epoch s>, "kind": <str>, ...}``;
  * ``kind`` names the record family.  Core families: ``run_start``,
    ``counter``, ``gauge``, ``timer``, plus the surface records —
    ``compile`` / ``step`` / ``summary`` / ``checkpoint_save`` /
    ``checkpoint_restore`` / ``sim_drift`` (training, model.py::fit),
    ``search_space`` / ``search_chunk`` / ``search_result`` /
    ``search_breakdown`` / ``pipeline_candidate`` / ``pipeline_decision``
    (sim/search.py), ``hlo_audit`` / ``bench`` (audit/bench), the
    execution-performance pair (round 6) — ``regrid_plan`` (the regrid
    planner's coalescing/hop accounting, parallel/regrid.py) and
    ``prefetch`` (device-prefetch stall residual, data/prefetch.py) —
    the MFU-waterfall pair (observability round 3): ``step_budget``
    (one step's wall time decomposed into compute / comm / input_stall /
    host_sync / checkpoint / residual buckets summing to the wall,
    obs/budget.py) and ``metrics`` (a mirror of each live-gauge snapshot
    the Prometheus exporter published, obs/metrics.py) —
    and the fault-tolerance family (robustness round): ``fault`` (an
    injected fault firing, a health-guard divergence detection, or a
    refused non-finite checkpoint), ``rollback`` (guard-driven restore
    of the last verified checkpoint), ``recovery`` (a clean window after
    rollback, or a read succeeding after retries), ``data_fault``
    (retried/skipped data reads, data/hdf5.py + data/imagenet.py),
    ``ckpt_fallback`` (restore cascading past a corrupt step,
    utils/checkpoint.py) and ``thread_leak`` (a worker join that timed
    out at shutdown);
  * :class:`RunLog` is the thread-safe sink; :class:`NullRunLog` (the
    module-level ``NULL``) is the disabled sink whose every method is a
    no-op, so instrumented code pays one predicate/attribute check when
    ``FFConfig.obs_dir`` is unset.  Event files are capped: when the
    current file reaches ``max_bytes`` the stream rolls over to a
    monotonically numbered sibling (``run.jsonl.1``, ``.2``, ...), so a
    long training run with per-op sampling enabled cannot grow one
    unbounded file;
  * :func:`read_events` is the single-file reader, :func:`run_files` /
    :func:`read_run` walk a rotated stream in write order;
    ``apps/report.py`` renders a run back into the summary tables humans
    read today, and ``obs/trace.py`` exports per-op timelines as
    Chrome/Perfetto traces with sim-vs-real drift attribution.

Spans and counters are process-global and need no handle:
``obs.span("ff:<layer>.<what>", **args)`` and ``obs.count(name, value)``
(obs/spans.py) keep a bounded in-memory aggregate and lie on the
profiler's clock; a surface with a live :class:`RunLog` writes ONE
``spans`` record from it when it finishes (``RunLog.spans()``).

Telemetry is strictly OFF the device hot path: records carry host-side
timestamps only and no instrumentation site may introduce a device sync
(``fit()`` buffers per-step wall times and writes records after the timed
loop).
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, Iterator, Optional

SCHEMA_VERSION = 1

# default size cap of one event file before rollover (64 MB); 0 disables
# rotation.  FFConfig.obs_max_bytes overrides per run.
DEFAULT_MAX_BYTES = 64 * 1024 * 1024


def new_run_id() -> str:
    """Sortable, collision-resistant run id: wall time + pid + 2 random
    bytes (two runs in the same second on the same host stay distinct)."""
    return "%s-%x-%s" % (time.strftime("%Y%m%d-%H%M%S"), os.getpid(),
                         os.urandom(2).hex())


class NullRunLog:
    """The disabled sink: every method is a no-op and ``enabled`` is
    False, so hot-path call sites cost one attribute check.  A single
    module-level instance (``NULL``) is shared."""

    enabled = False
    path = None
    run_id = None

    def event(self, kind: str, **fields) -> None:
        pass

    def counter(self, name: str, value: float = 1, **fields) -> None:
        pass

    def gauge(self, name: str, value: float, **fields) -> None:
        pass

    def timer(self, name: str, **fields):
        return contextlib.nullcontext()

    def spans(self) -> None:
        pass

    def close(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self) -> bool:
        return False


NULL = NullRunLog()


class RunLog:
    """Thread-safe JSONL event sink.

    One instance == one event stream (usually one file per run id; several
    surfaces of the same process — fit, search, bench — may share it, the
    ``surface`` field keeps them separable).  Writes are line-buffered and
    serialized under a lock, so concurrent emitters (e.g. data-loader
    threads) never interleave partial lines."""

    enabled = True

    def __init__(self, path: str, run_id: Optional[str] = None,
                 surface: str = "", meta: Optional[Dict[str, Any]] = None,
                 max_bytes: int = DEFAULT_MAX_BYTES):
        """``path`` is the stream's BASE file; once the current file
        reaches ``max_bytes`` (0 = never) writes continue in
        ``path.<n>`` with n increasing monotonically.  Re-opening an
        already-rotated stream resumes at its newest part."""
        self.path = path
        self.run_id = run_id or new_run_id()
        self.surface = surface
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._lock = threading.Lock()
        self._max_bytes = max(int(max_bytes or 0), 0)
        self._seq = 0
        while os.path.exists(f"{path}.{self._seq + 1}"):
            self._seq += 1
        self._f = open(self._part_path(), "a")
        self.event("run_start", schema=SCHEMA_VERSION,
                   **(dict(meta) if meta else {}))

    def _part_path(self) -> str:
        return self.path if self._seq == 0 else f"{self.path}.{self._seq}"

    # -- core emitters --------------------------------------------------

    def event(self, kind: str, **fields) -> None:
        rec = {"run": self.run_id, "ts": time.time(), "kind": kind}
        if self.surface:
            rec["surface"] = self.surface
        rec.update(fields)
        line = json.dumps(rec, default=_jsonable)
        with self._lock:
            if self._f.closed:
                return
            self._f.write(line + "\n")
            self._f.flush()
            if self._max_bytes and self._f.tell() >= self._max_bytes:
                # size-based rollover: close the full part, continue in
                # the next numbered sibling (readers walk run_files())
                self._f.close()
                self._seq += 1
                self._f = open(self._part_path(), "a")

    def counter(self, name: str, value: float = 1, **fields) -> None:
        self.event("counter", name=name, value=value, **fields)

    def gauge(self, name: str, value: float, **fields) -> None:
        self.event("gauge", name=name, value=value, **fields)

    @contextlib.contextmanager
    def timer(self, name: str, **fields):
        """A ``timer`` record, measured by :func:`span` ``ff:timer.<name>``
        so the interval also lies in the aggregate and, under a profiler
        session, on the device events' clock."""
        from flexflow_tpu.obs import spans

        sp = spans.span("ff:timer." + name)
        try:
            with sp:
                yield
        finally:
            self.event("timer", name=name, seconds=sp.seconds, **fields)

    def spans(self) -> None:
        """Write the one ``spans`` record: the process's span and
        counter aggregate as it stands (memory first, file at the end) —
        what ``fit``, the serve engine and ``apps/search.py`` call when
        they finish."""
        from flexflow_tpu.obs import spans

        self.event("spans", **spans.summary())

    # -- lifecycle ------------------------------------------------------

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# the span and counter API (obs/spans.py) under ``obs.<name>``, imported
# on first use: spans.py imports JAX, which the report tools that import
# this package for its JSONL readers never need
_SPANS_API = ("span", "count", "snapshot", "summary", "reset",
              "note_program", "program", "counter_at")


def __getattr__(name: str):
    if name in _SPANS_API:
        from flexflow_tpu.obs import spans

        value = globals()[name] = getattr(spans, name)
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _jsonable(o):
    """Last-resort encoder: numpy/jax scalars -> python numbers, tuples of
    them inside payloads -> lists, everything else -> repr (a telemetry
    write must never raise into the instrumented surface)."""
    try:
        return o.item()  # numpy / jax scalar
    except AttributeError:
        pass
    if isinstance(o, (set, frozenset)):
        return sorted(o)
    return repr(o)


def from_config(config, surface: str = "",
                meta: Optional[Dict[str, Any]] = None):
    """The one gate instrumented surfaces call: a live :class:`RunLog`
    when ``config.obs_dir`` is set (file ``<obs_dir>/<run_id>.jsonl``),
    else the shared ``NULL`` sink.  ``config.run_id`` (when set) names the
    run so several processes/surfaces can append to one stream."""
    obs_dir = getattr(config, "obs_dir", "") or ""
    if not obs_dir:
        return NULL
    run_id = getattr(config, "run_id", "") or new_run_id()
    return RunLog(os.path.join(obs_dir, f"{run_id}.jsonl"),
                  run_id=run_id, surface=surface, meta=meta,
                  max_bytes=getattr(config, "obs_max_bytes",
                                    DEFAULT_MAX_BYTES))


def run_files(path: str) -> list:
    """A run stream's files in write order: the base ``path`` plus its
    rotated parts ``path.1``, ``path.2``, ...  (``path`` itself may
    legitimately be missing when a caller points at a rotated part
    directly — only existing files are returned)."""
    out = [path] if os.path.exists(path) else []
    i = 1
    while os.path.exists(f"{path}.{i}"):
        out.append(f"{path}.{i}")
        i += 1
    return out


def read_run(path: str) -> Iterator[Dict[str, Any]]:
    """All records of a possibly-rotated run stream, in write order."""
    for p in run_files(path):
        yield from read_events(p)


def read_events(path: str) -> Iterator[Dict[str, Any]]:
    """Yield the records of a run JSONL in file order.  Malformed lines
    (a crashed writer's torn tail) are skipped, not raised — readers must
    be able to render a partial run."""
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict):
                yield rec
