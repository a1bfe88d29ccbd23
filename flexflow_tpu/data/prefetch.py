"""Double-buffered device prefetch: overlap host batch prep + H2D transfer
of step N+1 with step N's compute.

Before this module the training loop pulled each batch synchronously
inside the timed loop — host-side generation/decode and the sharded
``device_put`` both sat on the step's critical path.  The reference
overlaps the same work with Legion CPU processors and its loader's
prefetch queue (``-ll:cpu``, ops.cu:281-420); here a single background
thread pulls from the upstream iterator, commits each batch to devices
with the machine's batch sharding, and hands ready device arrays through
a depth-bounded queue (default 2 — classic double buffering: one batch
training, one staged).

Contracts the tests pin (tests/test_prefetch.py):

  * **determinism** — one worker thread, FIFO queue: batches arrive in
    exactly the upstream order;
  * **exception propagation** — an upstream (or placement) error is
    caught on the worker, carried through the queue, and re-raised in the
    consumer's ``__next__`` (never a hang, never a silent drop);
    ``StopIteration`` propagates the same way for finite upstreams;
  * **clean shutdown** — ``close()`` (or ``with``-exit, or GC) stops the
    worker promptly even when it is blocked on a full queue, and joins
    the thread.

The consumer-side stall clock (``stall_s``) accumulates the time
``__next__`` spent waiting on an empty queue — the residual input cost
the overlap could NOT hide.  ``fit()`` emits it as the ``prefetch`` obs
record and ``bench.py`` reports it as ``input_stall_s``.
"""

from __future__ import annotations

import queue
import threading
import warnings
from typing import Iterator

from flexflow_tpu import obs

_STOP_POLL_S = 0.1

# how long close() waits for the worker before declaring the thread
# leaked (module-level so tests can shrink it)
_JOIN_TIMEOUT_S = 2.0


class _Failure:
    """Queue sentinel carrying a worker-side exception to the consumer."""

    def __init__(self, exc: BaseException):
        self.exc = exc


class _End:
    """Queue sentinel: upstream iterator exhausted."""


class DevicePrefetcher:
    """Iterator wrapping ``upstream`` with background sharded placement.

    ``machine`` supplies the batch sharding (the data/ loaders'
    data-parallel convention); leaves that are already committed jax
    arrays pass through untouched, so wrapping a source that places its
    own batches (e.g. the pre-placed synthetic ring) costs nothing.
    ``machine=None`` disables placement entirely (pure read-ahead).
    """

    def __init__(self, upstream: Iterator, machine=None, depth: int = 2,
                 olog=None):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self.depth = depth
        self.stall_s = 0.0
        self.batches = 0
        self._placed = 0
        self.leaked = False
        self._olog = olog
        self._upstream = upstream
        self._sharding = None
        if machine is not None and machine.num_devices >= 1:
            from flexflow_tpu.data.synthetic import _batch_sharding

            self._sharding = _batch_sharding(machine)
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._exhausted = False
        self._thread = threading.Thread(
            target=self._worker, name="ff-device-prefetch", daemon=True)
        self._thread.start()

    # -- worker ----------------------------------------------------------

    def _place(self, batch):
        if self._sharding is None:
            return batch
        import jax

        put_bytes = 0

        def put(leaf):
            # already-committed device arrays (sources that place their
            # own batches) pass through; host arrays get the sharded put
            nonlocal put_bytes
            if isinstance(leaf, jax.Array) and getattr(
                    leaf, "sharding", None) is not None:
                return leaf
            put_bytes += int(getattr(leaf, "nbytes", 0))
            return jax.device_put(leaf, self._sharding)

        with obs.span("ff:runtime.prefetch_put", batch=self._placed) as sp:
            out = tuple(put(b) for b in batch) if isinstance(
                batch, (tuple, list)) else put(batch)
            sp.args["bytes"] = put_bytes
        self._placed += 1
        obs.count("runtime.prefetch_bytes", put_bytes)
        return out

    def _worker(self):
        while not self._stop.is_set():
            try:
                item = self._place(next(self._upstream))
            except StopIteration:
                item = _End()
            except BaseException as e:  # surfaced in the consumer
                item = _Failure(e)
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=_STOP_POLL_S)
                    break
                except queue.Full:
                    continue
            if isinstance(item, (_End, _Failure)):
                return

    # -- consumer --------------------------------------------------------

    def __iter__(self):
        return self

    def __next__(self):
        if self._exhausted:
            raise StopIteration
        if self._stop.is_set():
            raise RuntimeError("DevicePrefetcher is closed")
        with obs.span("ff:runtime.prefetch_wait", batch=self.batches) as sp:
            item = self._q.get()
        self.stall_s += sp.seconds
        if isinstance(item, _End):
            self._exhausted = True
            self.close()
            raise StopIteration
        if isinstance(item, _Failure):
            self._exhausted = True
            self.close()
            raise item.exc
        self.batches += 1
        return item

    def close(self) -> None:
        """Stop the worker (unblocking a put-in-progress) and join it.
        Idempotent; also runs at GC so an abandoned prefetcher never
        leaks its thread.  A join that times out (a worker stuck in the
        upstream iterator) is DETECTED and reported — previously the
        failure was silent and the thread leaked while shutdown claimed
        success."""
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        t = self._thread
        if t is not None and t.is_alive() \
                and t is not threading.current_thread():
            t.join(timeout=_JOIN_TIMEOUT_S)
            if t.is_alive() and not self.leaked:
                self.leaked = True
                warnings.warn(
                    f"DevicePrefetcher worker did not exit within "
                    f"{_JOIN_TIMEOUT_S:.1f}s (stuck in the upstream "
                    f"iterator?); leaking the daemon thread",
                    RuntimeWarning)
                if self._olog is not None \
                        and getattr(self._olog, "enabled", False):
                    self._olog.event("thread_leak",
                                     source="DevicePrefetcher",
                                     timeout_s=_JOIN_TIMEOUT_S)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def summary(self) -> dict:
        """The ``prefetch`` obs record body."""
        return {"depth": self.depth, "batches": self.batches,
                "input_stall_s": self.stall_s, "leaked": self.leaked}


def prefetch_batches(upstream: Iterator, machine=None,
                     depth: int = 2) -> DevicePrefetcher:
    """Convenience wrapper used by the data sources and drivers."""
    return DevicePrefetcher(upstream, machine=machine, depth=depth)
