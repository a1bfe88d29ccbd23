"""Checkpoint / resume subsystem.

The reference has NO weight checkpointing (SURVEY.md §5: only the *strategy*
is serializable, strategy.cc:62-86) — any failure restarts training from
scratch.  A complete framework needs durable training state, so this module
adds it as a first-class subsystem:

  * a checkpoint = (iteration, params, state, opt_state) + the model's
    Strategy, so a resumed run executes under the same per-layer
    parallelization;
  * atomic directory commit (write to ``<dir>/tmp.<step>``, fsync, rename to
    ``<dir>/step_<N>``) — a killed run never leaves a half-written
    checkpoint that resume would trust; stale ``tmp.<step>`` /
    ``step_*.old`` directories a crash mid-save left behind are swept on
    the next save/restore instead of accumulating forever;
  * **verified integrity** (robustness round): ``meta.json`` records a
    SHA-256 digest per payload file at save; :func:`verify_checkpoint`
    re-checks them, and restore (without an explicit step) CASCADES
    latest -> older past truncated/missing/corrupt steps, emitting a
    ``ckpt_fallback`` obs record — a flipped bit in ``arrays.npz`` costs
    one checkpoint interval, not the run;
  * a **finiteness gate**: ``save_checkpoint`` refuses (by default) to
    commit non-finite float leaves over good on-disk state
    (:class:`NonFiniteCheckpointError`), and pruning never deletes the
    newest step that still verifies clean — so a diverged run cannot
    rotate every healthy checkpoint out of existence;
  * restore is **sharding-aware**: when given the model, every param lands
    directly on its op's NamedSharding (same placement as ``FFModel.init``),
    so resume does not funnel large trees through one device.

Format: one ``arrays.npz`` of flattened ``a/b/c``-keyed leaves per tree,
plus ``meta.json`` recording each leaf's dtype and the file digests.
Plain numpy keeps the format dependency-free and inspectable; extension
dtypes (bfloat16, fp8) round-trip by re-viewing the raw bytes as the
recorded ml_dtypes dtype on load (np.savez alone degrades them to void).
Pre-digest checkpoints load unchanged (verification reports them as
unverifiable rather than corrupt).
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import warnings
from typing import Any, Dict, Optional, Tuple

import numpy as np

from flexflow_tpu import obs

_SEP = "/"


class CheckpointError(RuntimeError):
    """Base of the checkpoint subsystem's own failures."""


class CheckpointCorruptError(CheckpointError):
    """A requested checkpoint failed integrity verification (or every
    candidate did, when cascading)."""


class NonFiniteCheckpointError(CheckpointError):
    """``save_checkpoint`` refused to commit non-finite float state over
    good on-disk checkpoints (pass ``require_finite=False`` to force)."""


def _flatten(tree: Dict, prefix: str = "") -> Dict[str, Any]:
    flat = {}
    for k, v in tree.items():
        if _SEP in k:
            raise ValueError(f"checkpoint key {k!r} may not contain {_SEP!r}")
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            flat.update(_flatten(v, path + _SEP))
        else:
            flat[path] = v
    return flat


def _unflatten(flat: Dict[str, Any]) -> Dict:
    tree: Dict = {}
    for path, v in flat.items():
        keys = path.split(_SEP)
        node = tree
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = v
    return tree


def _step_dir(ckpt_dir: str, step: int) -> str:
    return os.path.join(ckpt_dir, f"step_{step:08d}")


def _list_steps(ckpt_dir: str) -> list:
    """Sorted committed checkpoint steps in ``ckpt_dir``."""
    if not os.path.isdir(ckpt_dir):
        return []
    steps = []
    for name in os.listdir(ckpt_dir):
        if name.startswith("step_") and not name.endswith(".old"):
            try:
                steps.append(int(name[5:]))
            except ValueError:
                continue
    return sorted(steps)


def _sweep_stale(ckpt_dir: str) -> None:
    """Remove leftovers of a crash mid-save: uncommitted ``tmp.<step>``
    staging dirs and ``step_*.old`` aside copies.  They were previously
    never cleaned up and accumulated forever."""
    if not os.path.isdir(ckpt_dir):
        return
    for name in os.listdir(ckpt_dir):
        if name.startswith("tmp.") or (name.startswith("step_")
                                       and name.endswith(".old")):
            shutil.rmtree(os.path.join(ckpt_dir, name), ignore_errors=True)


def latest_step(ckpt_dir: str) -> Optional[int]:
    """Highest completed checkpoint step in ``ckpt_dir``, or None."""
    steps = _list_steps(ckpt_dir)
    return steps[-1] if steps else None


def _file_sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _nonfinite_leaves(arrays: Dict[str, np.ndarray]) -> list:
    """Paths of float leaves holding NaN/Inf (int/bool leaves skipped;
    extension floats like bfloat16 are checked through their float32
    view when the ufunc lacks a native loop)."""
    bad = []
    for path, a in arrays.items():
        if a.dtype.kind in "iub":
            continue
        try:
            ok = bool(np.isfinite(a).all())
        except TypeError:
            ok = bool(np.isfinite(np.asarray(a, np.float32)).all())
        if not ok:
            bad.append(path)
    return bad


def verify_checkpoint(ckpt_dir: str, step: int) -> Tuple[bool, str]:
    """Integrity check of one committed step: directory + ``meta.json``
    present and parseable, every payload file present with a matching
    SHA-256 digest.  Returns ``(ok, reason)``; pre-digest checkpoints
    pass as ``"unverified (no digests)"`` for format compatibility."""
    d = _step_dir(ckpt_dir, step)
    if not os.path.isdir(d):
        return False, "missing directory"
    try:
        with open(os.path.join(d, "meta.json")) as f:
            meta = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return False, f"meta.json unreadable: {e}"
    if int(meta.get("step", -1)) != int(step):
        return False, (f"meta.json names step {meta.get('step')!r}, "
                       f"directory says {step}")
    if not os.path.exists(os.path.join(d, "arrays.npz")):
        return False, "arrays.npz missing"
    digests = meta.get("digests")
    if not digests:
        return True, "unverified (no digests; pre-digest format)"
    for name, want in digests.items():
        p = os.path.join(d, name)
        if not os.path.exists(p):
            return False, f"{name} missing"
        got = _file_sha256(p)
        if got != want:
            return False, f"{name} digest mismatch ({got[:12]} != {want[:12]})"
    return True, "ok"


def save_checkpoint(ckpt_dir: str, step: int, params: Dict, state: Dict,
                    opt_state: Dict, strategy=None, keep: int = 3,
                    require_finite: bool = True) -> str:
    """Write checkpoint atomically; prune to the newest ``keep`` steps
    (never deleting the newest step that still VERIFIES clean, so a
    corrupted latest cannot rotate the last good state away).  With
    ``require_finite`` (the default) non-finite float leaves abort the
    save BEFORE anything touches disk.  Returns the committed
    directory."""
    with obs.span("ff:runtime.checkpoint_save", step=int(step)):
        return _save_checkpoint(ckpt_dir, step, params, state, opt_state,
                                strategy, keep, require_finite)


def _save_checkpoint(ckpt_dir, step, params, state, opt_state, strategy,
                     keep, require_finite) -> str:
    os.makedirs(ckpt_dir, exist_ok=True)
    _sweep_stale(ckpt_dir)
    tmp = os.path.join(ckpt_dir, f"tmp.{step}")
    final = _step_dir(ckpt_dir, step)

    arrays: Dict[str, np.ndarray] = {}
    dtypes: Dict[str, str] = {}
    for tree_name, tree in (("params", params), ("state", state or {}),
                            ("opt", opt_state or {})):
        for path, leaf in _flatten(tree, tree_name + _SEP).items():
            a = np.asarray(leaf)
            arrays[path] = a
            dtypes[path] = str(a.dtype)
    if require_finite:
        bad = _nonfinite_leaves(arrays)
        if bad:
            raise NonFiniteCheckpointError(
                f"refusing to checkpoint non-finite state at step {step}: "
                f"{len(bad)} leaves, e.g. {bad[:3]} (pass "
                f"require_finite=False to force)")

    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
    if strategy is not None and len(strategy):
        strategy.save(os.path.join(tmp, "strategy.json"))
    # per-file content digests, recorded in meta.json so restore can
    # distinguish a torn/bit-flipped checkpoint from a good one
    digests = {name: _file_sha256(os.path.join(tmp, name))
               for name in sorted(os.listdir(tmp))}
    meta = {"step": int(step), "format": 2, "dtypes": dtypes,
            "digests": digests}
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump(meta, f)

    # durable commit: flush file data, then the tmp dir entry, then rename,
    # then flush the parent so the rename itself is on disk
    for name in os.listdir(tmp):
        fd = os.open(os.path.join(tmp, name), os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
    for d in (tmp,):
        fd = os.open(d, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
    # never delete the old committed dir before the new one is in place:
    # move it aside, rename tmp in, then drop the aside copy
    aside = None
    if os.path.exists(final):
        aside = final + ".old"
        if os.path.exists(aside):
            shutil.rmtree(aside)
        os.rename(final, aside)
    os.rename(tmp, final)
    fd = os.open(ckpt_dir, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)
    if aside:
        shutil.rmtree(aside, ignore_errors=True)

    # deterministic fault injection (utils/faultinject.py): damage the
    # COMMITTED copy — a torn write / bit flip the digests must catch
    from flexflow_tpu.utils import faultinject

    inj = faultinject.get()
    if inj.enabled:
        ap = os.path.join(final, "arrays.npz")
        if inj.fire("ckpt_truncate", site=final):
            with open(ap, "r+b") as f:
                f.truncate(max(os.path.getsize(ap) // 2, 1))
        if inj.fire("ckpt_corrupt", site=final):
            with open(ap, "r+b") as f:
                f.seek(os.path.getsize(ap) // 2)
                b = f.read(1)
                f.seek(-1, os.SEEK_CUR)
                f.write(bytes([b[0] ^ 0xFF]))

    if keep:
        steps = _list_steps(ckpt_dir)
        protect = set(steps[-keep:])
        for s in reversed(steps):
            ok, _ = verify_checkpoint(ckpt_dir, s)
            if ok:
                protect.add(s)  # the newest verified-good step survives
                break
        for s in steps:
            if s not in protect:
                shutil.rmtree(_step_dir(ckpt_dir, s), ignore_errors=True)
    return final


def _restore_dtype(arr: np.ndarray, stored: Optional[str]) -> np.ndarray:
    """Re-view raw bytes as the recorded extension dtype (bfloat16/fp8 …)
    when np.load degraded it to void."""
    if stored is None or str(arr.dtype) == stored:
        return arr
    import ml_dtypes

    if hasattr(ml_dtypes, stored):
        return arr.view(np.dtype(getattr(ml_dtypes, stored)))
    return arr.astype(stored)


def _load_step(ckpt_dir: str, step: int, model=None
               ) -> Tuple[int, Dict, Dict, Dict]:
    """Load one committed step (no verification, no cascade)."""
    d = _step_dir(ckpt_dir, step)
    with open(os.path.join(d, "meta.json")) as f:
        meta = json.load(f)
    stored_dtypes = meta.get("dtypes", {})
    with np.load(os.path.join(d, "arrays.npz")) as z:
        flat = {k: _restore_dtype(z[k], stored_dtypes.get(k))
                for k in z.files}

    trees = {"params": {}, "state": {}, "opt": {}}
    for path, arr in flat.items():
        tree_name, rest = path.split(_SEP, 1)
        trees[tree_name][rest] = arr
    params = _unflatten(trees["params"])
    state = _unflatten(trees["state"])
    opt_state = _unflatten(trees["opt"])

    if model is not None:
        import jax

        shardings = {}
        for op in model.layers:
            if op.param_key not in shardings:
                s = op.param_shardings(model.machine)
                if s:
                    shardings[op.param_key] = s

        def put(v, shard):
            if shard is None:
                return jax.device_put(v)
            if getattr(shard, "is_fully_addressable", True):
                return jax.device_put(v, shard)
            # multi-host restore (elastic_rejoin): device_put cannot
            # scatter a host array onto devices owned by other
            # processes; build the global array from each process's
            # local shards instead — every host loaded the same file
            arr = np.asarray(v)
            return jax.make_array_from_callback(
                arr.shape, shard, lambda idx: arr[idx])

        def place(tree):
            placed = {}
            for key, sub in tree.items():
                ops_shard = shardings.get(key, {})
                # mixed-precision master leaves (<leaf>__master in the
                # opt tree, see model._MASTER_SUFFIX) take the base
                # param leaf's sharding — shardings are dtype-agnostic
                placed[key] = {
                    k: put(v, ops_shard.get(
                        k, ops_shard.get(k[:-len("__master")]
                                         if k.endswith("__master")
                                         else k)))
                    for k, v in sub.items()}
            return placed

        params = place(params)
        opt_state = place(opt_state)
        state = jax.tree.map(jax.device_put, state)
    return step, params, state, opt_state


def restore_checkpoint(ckpt_dir: str, model=None,
                       step: Optional[int] = None, verify: bool = True,
                       olog=None) -> Tuple[int, Dict, Dict, Dict]:
    """Load (step, params, state, opt_state).  With ``model`` given, params
    and opt leaves are placed on the owning op's sharding and state on the
    op's grid, exactly as ``FFModel.init`` would place them.

    Without an explicit ``step`` the restore CASCADES: the latest step is
    verified (digests, presence, parseability) and actually loaded; on any
    failure the next-older step is tried, a ``ckpt_fallback`` obs record
    is emitted on ``olog``, and only when EVERY committed step fails does
    this raise :class:`CheckpointCorruptError`.  An explicit ``step`` is
    verified but never cascaded (the caller asked for that one)."""
    olog = olog if olog is not None else obs.NULL
    _sweep_stale(ckpt_dir)
    if step is not None:
        if verify:
            ok, why = verify_checkpoint(ckpt_dir, step)
            if not ok:
                raise CheckpointCorruptError(
                    f"checkpoint step {step} under {ckpt_dir!r} failed "
                    f"verification: {why}")
        return _load_step(ckpt_dir, step, model)
    steps = _list_steps(ckpt_dir)
    if not steps:
        raise FileNotFoundError(f"no checkpoint under {ckpt_dir!r}")
    newest = steps[-1]
    failures = []
    for s in reversed(steps):
        if verify:
            ok, why = verify_checkpoint(ckpt_dir, s)
            if not ok:
                failures.append((s, why))
                continue
        try:
            out = _load_step(ckpt_dir, s, model)
        except Exception as e:  # torn npz, bad json, ... -> next candidate
            failures.append((s, f"load failed: {e}"))
            continue
        if s != newest:
            olog.event("ckpt_fallback", dir=ckpt_dir, from_step=newest,
                       to_step=s,
                       skipped=[{"step": fs, "reason": fw}
                                for fs, fw in failures])
            warnings.warn(
                f"checkpoint fallback: step {newest} -> {s} under "
                f"{ckpt_dir!r} ({'; '.join(f'step {fs}: {fw}' for fs, fw in failures)})",
                RuntimeWarning)
        return out
    raise CheckpointCorruptError(
        f"every checkpoint under {ckpt_dir!r} failed verification/load: "
        + "; ".join(f"step {fs}: {fw}" for fs, fw in failures))


def snapshot_tree(tree: Dict) -> Dict:
    """Host-side deep copy of a (possibly device-resident) checkpoint
    tree: every leaf materialized as a plain numpy array.  This is the
    async writer's consistency point — the copy happens at the caller's
    host-sync boundary, so the background serialization can never
    observe a leaf the NEXT training step has already donated/mutated."""
    out: Dict = {}
    for k, v in (tree or {}).items():
        # np.array(copy=True): np.asarray of a HOST array is a view, and
        # a view is exactly the torn-snapshot hazard this exists to close
        out[k] = snapshot_tree(v) if isinstance(v, dict) \
            else np.array(v, copy=True)
    return out


class AsyncCheckpointWriter:
    """Background checkpoint committer: serialization, digest computation
    and the fsync'd atomic directory commit run on ONE worker thread, off
    the training step's critical path.

    Contract (robustness round, elastic tentpole):

      * ``submit()`` snapshots the device trees to host numpy at the
        call site (the only part that must happen at the sync boundary —
        the next step donates those buffers) and enqueues the write; at
        most ONE save is in flight, so a submit that arrives while the
        previous write is still running first waits for it (this only
        costs anything when a write is slower than a checkpoint
        interval);
      * the committed bytes are BIT-IDENTICAL to a synchronous
        :func:`save_checkpoint` of the same state — the worker calls the
        exact same function on the snapshot;
      * a worker-side :class:`NonFiniteCheckpointError` (or any other
        save failure) never kills the run: it is counted in ``faults``,
        logged, and emitted as a ``fault`` obs record, exactly like the
        synchronous path's handling;
      * ``wait()`` blocks until the queue is drained — fit() calls it
        before a rollback restore (the restore must see the newest
        commit) and at the final save; ``close()`` waits and joins.

    ``inflight`` (0 or 1) is exported as the ``ff_ckpt_async_inflight``
    gauge.  Every completed write emits a ``ckpt_async`` obs record with
    the submit->commit latency so the overlap is auditable."""

    def __init__(self, olog=None, log=None, keep: int = 3,
                 require_finite: bool = True):
        import queue
        import threading

        self.olog = olog if olog is not None else obs.NULL
        self.log = log or (lambda *a: None)
        self.keep = keep
        self.require_finite = require_finite
        self.inflight = 0
        self.saves = 0
        self.faults = 0
        self.last_step: Optional[int] = None
        self._q: "queue.Queue" = queue.Queue(maxsize=1)
        self._idle = threading.Event()
        self._idle.set()
        self._lock = threading.Lock()
        self._thread = threading.Thread(
            target=self._worker, name="ff-ckpt-async", daemon=True)
        self._thread.start()

    # -- producer side (the training loop) ---------------------------

    def submit(self, ckpt_dir: str, step: int, params, state, opt_state,
               strategy=None) -> None:
        """Snapshot + enqueue one checkpoint write.  Blocks only if the
        PREVIOUS write has not finished (one in flight, ever)."""
        self.wait()
        import time as _time

        # the host snapshot is the part of an async save the training
        # loop waits for
        with obs.span("ff:runtime.checkpoint_save", step=int(step),
                      mode="snapshot"):
            job = {
                "dir": ckpt_dir, "step": int(step),
                "params": snapshot_tree(params),
                "state": snapshot_tree(state),
                "opt": snapshot_tree(opt_state),
                "strategy": strategy, "t_submit": _time.perf_counter(),
            }
        with self._lock:
            self.inflight += 1
        self._idle.clear()
        self._q.put(job)

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until no write is in flight.  True when drained."""
        return self._idle.wait(timeout=timeout)

    def close(self, timeout: Optional[float] = None) -> None:
        """Drain, then stop and join the worker.  Idempotent."""
        self.wait(timeout=timeout)
        if self._thread.is_alive():
            self._q.put(None)
            self._thread.join(timeout=timeout or 10.0)

    # -- worker side --------------------------------------------------

    def _worker(self):
        import time as _time

        while True:
            job = self._q.get()
            if job is None:
                self._idle.set()
                return
            try:
                try:
                    save_checkpoint(job["dir"], job["step"], job["params"],
                                    job["state"], job["opt"],
                                    job["strategy"], keep=self.keep,
                                    require_finite=self.require_finite)
                    dt = _time.perf_counter() - job["t_submit"]
                    with self._lock:
                        self.saves += 1
                        self.last_step = job["step"]
                    self.olog.event("checkpoint_save", step=job["step"],
                                    seconds=dt, dir=job["dir"],
                                    mode="async")
                    self.olog.event("ckpt_async", step=job["step"],
                                    commit_s=dt, saves=self.saves,
                                    faults=self.faults)
                except NonFiniteCheckpointError as e:
                    with self._lock:
                        self.faults += 1
                    self.olog.event("fault", source="checkpoint",
                                    fault="nonfinite_state",
                                    step=job["step"], error=str(e))
                    self.log(f"warning: skipped async checkpoint at "
                             f"iteration {job['step']}: {e}")
                except Exception as e:  # never kill the run from here
                    with self._lock:
                        self.faults += 1
                    self.olog.event("fault", source="checkpoint",
                                    fault="async_save_failed",
                                    step=job["step"], error=str(e))
                    self.log(f"warning: async checkpoint at iteration "
                             f"{job['step']} failed: {e}")
            finally:
                with self._lock:
                    self.inflight -= 1
                    if self.inflight == 0:
                        self._idle.set()


def load_strategy(ckpt_dir: str, step: Optional[int] = None):
    """The Strategy a checkpoint was trained under, or None."""
    from flexflow_tpu.strategy import Strategy

    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            return None
    path = os.path.join(_step_dir(ckpt_dir, step), "strategy.json")
    return Strategy.load(path) if os.path.exists(path) else None
