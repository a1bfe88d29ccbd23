"""The serving executor: forward-only dispatch, decode, autoscale, drain.

One :class:`ServeEngine` owns a live model and its placed (params,
state) and runs two service shapes through the SAME compiled machinery
training uses (``FFModel.apply`` under ``make_predict_step`` — per-op
strategies, placed/grouped dispatch, the regrid planner, donation on the
request activations):

  * :meth:`run` — transformer autoregressive decode with continuous
    batching: requests join the running ``(max_batch, seq)`` rectangle
    the step a slot frees, greedy argmax on the causal log-probs at each
    sequence's last position, EOS/token-budget slot reclaim, and a
    sharded KV cache (serve/kv_cache.py) filled from the forward's own
    per-layer attention inputs;
  * :meth:`run_forward` — batched forward-only service for CNN/NMT:
    padded fixed-shape batches staged through
    :class:`~flexflow_tpu.data.prefetch.DevicePrefetcher` (host assembly
    + H2D overlapped with device compute, the training staging pattern).

Time is VIRTUAL (serve/loadgen.py): the clock advances by
``step_time_s`` per decode step, so admission order, latencies,
watermark triggers and the summary metrics are bit-deterministic under a
seeded load.  Wall time is tracked separately and reported as
information.

**Autoscaling** reuses the elastic runtime's primitives directly
(utils/elastic.py — the ROADMAP's "the elastic runtime is the autoscaler
for free"): at decode-step boundaries, ``idle_boundaries`` consecutive
empty boundaries shrink the mesh to ``shrink_to`` devices (gather state
-> ``MachineModel.shrink`` -> budgeted re-search -> rebuild -> live
regrid), and queue depth >= ``queue_hi`` with parked devices grows it
back — each resize is one ``serve_resize`` obs record.  **Drain**: a
SIGTERM flag (utils/elastic.install_drain_handler) stops admission, the
in-flight slots finish and the engine returns cleanly — never-admitted
requests are reported as ``unserved``, not dropped.

**Per-request tracing**: the engine stamps ``first_token_v`` on each
request at the decode boundary its first generated token lands, so
every ``serve_request`` record (and the run summary) carries the
TTFT/TPOT split alongside total latency — TTFT (arrival -> first token)
is what an interactive user feels, TPOT (the decode tail per remaining
token) is what the decode loop costs.  ``serve_batch`` records carry
the KV-cache occupancy (``kv_tokens``/``kv_frac``) next to queue depth
and active slots, which ``obs/trace.py::serve_trace_events`` renders as
Perfetto counter lanes.

Obs records: ``serve_request`` (one per completed request, with
``ttft_s``/``tpot_s``), ``serve_batch`` (one per decode step / forward
batch, with KV occupancy), ``serve_resize`` (one per autoscale event),
``serve_summary`` (one per run, with TTFT/TPOT percentiles).
Prometheus gauges: ``ff_qps``, ``ff_queue_depth``, ``ff_latency_p50_s``,
``ff_latency_p99_s``, ``ff_ttft_p50_s``, ``ff_ttft_p99_s``,
``ff_tpot_p50_s``, ``ff_requests_total``, plus the
``ff_request_latency_s`` / ``ff_request_ttft_s`` histograms
(fixed log-spaced buckets, obs/metrics.py).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from flexflow_tpu import obs
from flexflow_tpu.serve.batcher import (ContinuousBatcher, RequestQueue,
                                        batch_requests)
from flexflow_tpu.serve.kv_cache import KVCache, KVCacheLayout
from flexflow_tpu.serve.loadgen import Request
from flexflow_tpu.utils import faultinject

# default virtual service time per decode step / forward batch, used
# when the strategy artifact carries no predicted forward time
DEFAULT_STEP_TIME_S = 0.01

# virtual slowdown an injected ``slow_replica`` fault applies to one
# decode step (a straggler, not a death — the hedged-decode adversary)
SLOW_REPLICA_FACTOR = 4.0


def _percentile(values: Sequence[float], q: float) -> float:
    if not values:
        return float("nan")
    return float(np.percentile(np.asarray(values, np.float64), q))


class ServeEngine:
    """Continuous-batching inference over one live FFModel.

    ``rebuild(ff_config, machine)`` is the same factory the elastic
    training path takes — without it autoscaling is disabled (the engine
    still serves, fixed-size).  ``queue_hi`` / ``idle_boundaries`` /
    ``shrink_to`` are the watermarks; 0 disables the corresponding
    trigger."""

    def __init__(self, model, rebuild=None, *, olog=None, metrics=None,
                 log=print, step_time_s: Optional[float] = None,
                 queue_hi: int = 0, idle_boundaries: int = 0,
                 shrink_to: int = 0, kv_window: Optional[int] = None,
                 pad_id: int = 0, phase: str = "full", pool: str = ""):
        if phase not in ("full", "prefill", "decode"):
            raise ValueError(
                f"phase must be 'full', 'prefill' or 'decode', "
                f"got {phase!r}")
        self.model = model
        self.rebuild = rebuild
        self.olog = olog if olog is not None else obs.NULL
        self.metrics = metrics
        self.log = log
        # disaggregation (serve/router.py): a "prefill" engine hands
        # every request off after its first generated token (the prompt
        # pass), a "decode" engine admits handed-off requests with their
        # carried tokens + imported KV rows; "full" is the single-pool
        # engine, unchanged.  ``pool`` labels this engine's obs records
        # and gauges ("" for single-pool keeps the records unlabeled).
        self.phase = phase
        self.pool = pool or ("" if phase == "full" else phase)
        self.queue_hi = int(queue_hi)
        self.idle_boundaries = int(idle_boundaries)
        self.shrink_to = int(shrink_to)
        self.kv_window = kv_window
        self.pad_id = int(pad_id)
        self.max_batch = int(model.config.batch_size)
        self.max_len = int(model._inputs[0].shape[1]) \
            if model._inputs[0].ndim >= 2 else 1
        self.step_time_s = float(step_time_s) if step_time_s else \
            self._predicted_step_time()
        self.resizes: List[Dict] = []
        self._sess: Optional[Dict] = None   # open start()/finish() session
        self._parked: List = []       # device OBJECTS out of service
        self.params = None
        self.state = None
        self.kv_cache: Optional[KVCache] = None
        self._compile()

    # ------------------------------------------------------------------
    # compilation / state

    def _predicted_step_time(self) -> float:
        pred = getattr(getattr(self.model.config, "strategies", None),
                       "predicted", None) or {}
        serve = pred.get("serve") or {}
        if self.phase != "full":
            # per-phase searched block (serve.prefill / serve.decode,
            # stamped by apps/search.py --serve --disagg)
            sub = serve.get(self.phase) or {}
            t = sub.get("step_time_s")
            if t:
                return float(t)
        t = serve.get("forward_step_s")
        return float(t) if t else DEFAULT_STEP_TIME_S

    def _attention_ops(self) -> List:
        from flexflow_tpu.ops.attention import MultiHeadAttention

        return [op for op in self.model.layers
                if isinstance(op, MultiHeadAttention)]

    def _compile(self, carry: Optional[Dict] = None) -> None:
        """(Re)build the predict step, the KV layout and the host K/V
        projection weights for the CURRENT model — called at init and
        after every resize."""
        model = self.model
        if carry is not None:
            self.params, self.state = carry["params"], carry["state"]
        elif self.params is None:
            self.params, self.state = model.init(model.config.seed)
        self._attn_ops = self._attention_ops()
        loss_tid = model._loss_op().output.tid
        tids = (loss_tid,) + tuple(op.inputs[0].tid
                                   for op in self._attn_ops)
        self._predict = model.make_predict_step(output_tids=tids)
        # host mirrors of each layer's K/V projections, used to fill the
        # cache from the forward's attention inputs (exact by
        # construction: the same einsum ops/attention.py projects with)
        self._kv_w = []
        for op in self._attn_ops:
            p = model._member_params(self.params, op)
            self._kv_w.append((np.asarray(p["wk"]).astype(np.float32),
                               np.asarray(p["wv"]).astype(np.float32)))
        layout = KVCacheLayout.from_model(
            model, self.max_batch, self.kv_window,
            strategy=getattr(model.config, "strategies", None))
        self.kv_layout = layout
        self.kv_cache = KVCache(layout) if layout is not None else None
        self._kv_filled = [0] * self.max_batch

    def _zero_extra_inputs(self) -> List[np.ndarray]:
        """Zero arrays for every model input past the first (the
        transformer's ``labels`` feed — read by the softmax op's graph
        but consumed only by ``loss()``, which serving never calls)."""
        out = []
        for t in self.model._inputs[1:]:
            out.append(np.zeros(t.shape, t.dtype))
        return out

    # ------------------------------------------------------------------
    # decode service

    def run(self, requests: Sequence[Request],
            drain: Optional[Dict] = None) -> Dict:
        """Serve ``requests`` to completion (or drain) and return the
        summary dict (also emitted as the ``serve_summary`` record).

        Implemented as :meth:`start` + :meth:`step_once` to exhaustion +
        :meth:`finish` — the fleet coordinator drives the same three
        methods directly to interleave several jobs' decode steps in
        quanta on one process."""
        self.start(requests, drain=drain)
        while self.step_once():
            pass
        return self.finish()

    def start(self, requests: Sequence[Request],
              drain: Optional[Dict] = None,
              open_ended: bool = False) -> None:
        """Open a decode session over ``requests``; loop state lives on
        the engine until :meth:`finish`.  An ``open_ended`` session
        never self-closes on an empty queue — the router keeps feeding
        it via :meth:`push` and decides when it is over."""
        self._sess = {
            "t_wall0": time.perf_counter(),
            "queue": RequestQueue(requests),
            "batcher": ContinuousBatcher(self.max_batch, self.max_len),
            "vnow": 0.0, "steps": 0, "idle_streak": 0,
            "draining": False, "completed": [], "unserved": [],
            "extra": self._zero_extra_inputs(), "drain": drain,
            "done": False, "open_ended": bool(open_ended),
            "handoffs": [],
        }

    # -- router-facing session surface (serve/router.py) ----------------

    def push(self, req: Request) -> None:
        """Feed one more request into the open session's queue (the
        router's admission / handoff path)."""
        s = self._sess
        if s is None:
            raise RuntimeError("serve: no open session — call start() "
                               "before push()")
        s["queue"].push(req)

    def advance_to(self, v: float) -> None:
        """Advance the session's virtual clock to the router's global
        event time (never backwards)."""
        s = self._sess
        if s is not None and v > s["vnow"]:
            s["vnow"] = float(v)

    def session_vnow(self) -> Optional[float]:
        """The open session's virtual now (None when none is open) —
        the router's dispatch timestamp for this engine's handoffs."""
        s = self._sess
        return float(s["vnow"]) if s is not None else None

    def crash(self) -> Dict:
        """Kill the open session in place — the injected
        ``replica_crash`` path (serve/router.py).  Everything resident
        dies with the replica: in-flight slots lose their imported KV
        rows (their requests leave carrying every token generated so
        far, ready for the router's re-prefill ``kv_rebuild``), queued
        handoffs are returned with payloads intact (retransmittable,
        the bytes never left the host), and the pre-crash
        completion/step counts are handed to the router — a revived
        engine's :meth:`finish` only covers its NEW session.  Revival
        is a fresh :meth:`start`."""
        s = self._sess
        if s is None:
            raise RuntimeError("serve: no open session to crash")
        batcher = s["batcher"]
        in_flight: List[Request] = []
        for slot_idx, slot in list(batcher.active()):
            req = slot.req
            req.carried_tokens = slot.tokens[len(req.tokens):]
            req.kv_payload = None  # the imported rows died with the mesh
            batcher.release(slot_idx)
            in_flight.append(req)
        queued = s["queue"].drain()
        out = {"in_flight": in_flight, "queued": queued,
               "completed": list(s["completed"]),
               "steps": int(s["steps"]), "vnow": float(s["vnow"])}
        if self.kv_cache is not None:
            for i in range(self.max_batch):
                self.kv_cache.reclaim(i)
        self._kv_filled = [0] * self.max_batch
        self._sess = None
        return out

    def next_ready_v(self) -> Optional[float]:
        """The earliest virtual instant this session can do work: its
        current vnow while slots are in flight, the next queued
        (effective) arrival while idle, None when it has nothing at
        all — the router's event-selection signal."""
        s = self._sess
        if s is None:
            return None
        if s["batcher"].num_active():
            return float(s["vnow"])
        nxt = s["queue"].next_arrival()
        if nxt is None:
            return None
        return float(max(s["vnow"], nxt))

    def take_handoffs(self) -> List[Request]:
        """Pop the requests this (prefill) session handed off since the
        last call — each carries ``carried_tokens`` + ``kv_payload``,
        ready for a decode engine's queue."""
        s = self._sess
        if s is None:
            return []
        out = s["handoffs"]
        s["handoffs"] = []
        return out

    def load(self) -> int:
        """Queued + in-flight work in the open session — the router's
        least-loaded admission signal."""
        s = self._sess
        if s is None:
            return 0
        return int(s["queue"].pending()) + int(s["batcher"].num_active())

    def drain_queue(self) -> List[Request]:
        """Remove and return every still-queued request (the router's
        drain path: queued work is unserved, in-flight work finishes)."""
        s = self._sess
        return s["queue"].drain() if s is not None else []

    def session_completed(self) -> List[Request]:
        """The open session's completed requests so far (the router
        reads these before :meth:`finish` to merge pool results)."""
        s = self._sess
        return list(s["completed"]) if s is not None else []

    def pending(self) -> bool:
        """Work remains in the open session (queued or in-flight)."""
        s = getattr(self, "_sess", None)
        if s is None or s["done"]:
            return False
        return bool(s["queue"].pending() or s["batcher"].num_active())

    def queue_depth(self) -> int:
        """Arrived-but-unadmitted depth at the session's virtual now —
        the coordinator's load signal."""
        s = getattr(self, "_sess", None)
        return int(s["queue"].depth(s["vnow"])) if s is not None else 0

    def session_steps(self) -> int:
        """Decode steps taken by the open session (0 when none is
        open) — the step counter the fleet job stamps on a directed
        resize."""
        s = self._sess
        return int(s["steps"]) if s is not None else 0

    def step_once(self) -> bool:
        """One scheduling boundary of the open session: drain check,
        admission, watermark triggers, then at most one decode step.
        Returns True while work remains, False once the session is
        exhausted (call :meth:`finish` then)."""
        s = self._sess
        if s is None:
            raise RuntimeError("serve: no open session — call start() "
                               "before step_once()")
        if s["done"]:
            return False
        queue, batcher = s["queue"], s["batcher"]
        if not (queue.pending() or batcher.num_active()):
            if not s["open_ended"]:
                s["done"] = True
            return False
        drain = s["drain"]
        if drain is not None and drain.get("requested") \
                and not s["draining"]:
            s["draining"] = True
            s["unserved"] = queue.drain()
            self.log(f"serve: drain requested — finishing "
                     f"{batcher.num_active()} in-flight request(s), "
                     f"{len(s['unserved'])} queued request(s) unserved")
        vnow = s["vnow"]
        admitted = [] if s["draining"] else batcher.admit(queue, vnow)
        if self.phase == "decode" and self.kv_cache is not None:
            # handed-off requests arrive with their prefill pool's
            # exported KV rows: import them under THIS layout's ring so
            # the forward only fills positions generated here
            for slot_idx in admitted:
                slot = batcher.slots[slot_idx]
                if slot is not None and slot.req.kv_payload is not None:
                    filled = self.kv_cache.import_request(
                        slot_idx, slot.req.kv_payload)
                    self._kv_filled[slot_idx] = filled
                    slot.req.kv_payload = None
        depth = queue.depth(vnow)
        if (self.queue_hi > 0 and depth >= self.queue_hi
                and self._parked and not s["draining"]):
            self._resize("grow", s["steps"], vnow, depth,
                         s["idle_streak"])
            # the regrown mesh serves the backlog from the next step
            admitted += batcher.admit(queue, vnow)
            depth = queue.depth(vnow)
        if batcher.num_active() == 0:
            nxt = queue.next_arrival()
            if nxt is None:
                if not s["open_ended"]:
                    s["done"] = True
                return False  # drained queue, no in-flight work
            # idle boundary: no work until the next arrival
            s["idle_streak"] += 1
            if (self.idle_boundaries > 0
                    and s["idle_streak"] >= self.idle_boundaries
                    and not self._parked and not s["draining"]):
                self._resize("shrink", s["steps"], vnow, depth,
                             s["idle_streak"])
            if (self.idle_boundaries <= 0
                    or s["idle_streak"] > self.idle_boundaries):
                s["vnow"] = max(vnow, nxt)  # nothing left to trigger
            else:
                s["vnow"] = min(vnow + self.step_time_s, nxt)
            return True
        s["idle_streak"] = 0

        # one decode step over the full rectangle
        active = batcher.active()
        with obs.span("ff:serve.step", step=s["steps"] + 1,
                      active=len(active)):
            vnow = self._decode_step(s, active, vnow)
        for slot_idx, req in batcher.reclaim(vnow):
            if self.kv_cache is not None:
                self.kv_cache.reclaim(slot_idx)
            self._kv_filled[slot_idx] = 0
            s["completed"].append(req)
            self._observe_request(req)
            self.olog.event(
                "serve_request", rid=req.rid, arrival_v=req.arrival_v,
                admit_v=req.admit_v, first_token_v=req.first_token_v,
                done_v=req.done_v, latency_s=req.latency_s,
                ttft_s=req.ttft_s, tpot_s=req.tpot_s,
                prompt_len=len(req.tokens),
                new_tokens=len(req.reply or ()), wall_s=req.wall_s,
                pool=self.pool)
        self.olog.event("serve_batch", step=s["steps"], vnow=vnow,
                        active=len(active), admitted=len(admitted),
                        queue_depth=depth,
                        devices=self.model.machine.num_devices,
                        pool=self.pool,
                        step_time_s=self.step_time_s,
                        **self._kv_occupancy())
        self._update_gauges(s["completed"], depth, vnow)
        return True

    def _to_host(self, x) -> np.ndarray:
        """A device array on the host, its bytes counted: every copy the
        decode step makes goes through here."""
        out = np.asarray(x)
        obs.count("serve.host_bytes", out.nbytes)
        return out

    def _decode_step(self, s, active, vnow: float) -> float:
        """The forward, the copy to the host, the token choice and the
        KV fill of one decode step (the children of ``ff:serve.step``);
        returns the virtual time its tokens land at.  ``ff:serve.forward``
        times the dispatch alone and ``ff:serve.to_host`` owns the wait
        for the device (no span adds a sync: the device's own share of a
        step is the trace's to say)."""
        batcher = s["batcher"]
        pre_lengths = {i: sl.length for i, sl in active}
        tokens = batcher.token_matrix(self.pad_id)
        with obs.span("ff:serve.forward") as fwd:
            outs = self._predict(self.params, self.state, tokens,
                                 *s["extra"])
        with obs.span("ff:serve.to_host") as copy:
            logprobs = self._to_host(outs[0])
        step_wall = fwd.seconds + copy.seconds
        with obs.span("ff:serve.kv_fill"):
            self._fill_kv(outs[1:], active, pre_lengths)
        step_s = self.step_time_s
        if self.phase == "decode":
            # injected straggler: this step's virtual service time
            # stretches, delaying every token it lands — the p99 tail
            # the hedged-decode mode protects against.  Host-side only:
            # with no injector armed the branch is byte-inert.
            inj = faultinject.get()
            if inj.enabled and inj.fire("slow_replica", site=self.pool):
                step_s *= SLOW_REPLICA_FACTOR
        done_v = vnow + step_s  # this step's tokens land here
        with obs.span("ff:serve.sample"):
            for slot_idx, slot in active:
                nxt_tok = int(np.argmax(logprobs[slot_idx,
                                                 slot.length - 1]))
                slot.req.wall_s += step_wall
                batcher.record_token(slot_idx, nxt_tok)
                if slot.generated == 1:
                    # the request's FIRST token materialized this step —
                    # the TTFT stamp every serve_request record carries.
                    # A handed-off request re-enters the decode pool with
                    # ``generated == len(carried_tokens) >= 1`` already,
                    # so the prefill pool's stamp is never overwritten.
                    slot.req.first_token_v = done_v
            obs.count("serve.tokens_out", len(active))
        s["vnow"] = vnow = done_v
        s["steps"] += 1
        if self.phase == "prefill":
            # the prompt pass is done: every still-running slot leaves
            # this pool carrying its generated token(s) and its exported
            # KV rows — the router routes it to a decode replica.
            # (Slots that finished outright — 1-token budget or instant
            # EOS — fall through to the normal reclaim below.)
            for slot_idx, slot in active:
                if slot.done:
                    continue
                req = slot.req
                req.carried_tokens = slot.tokens[len(req.tokens):]
                if self.kv_cache is not None:
                    req.kv_payload = self.kv_cache.export_request(
                        slot_idx)
                    self.kv_cache.reclaim(slot_idx)
                self._kv_filled[slot_idx] = 0
                batcher.release(slot_idx)
                s["handoffs"].append(req)
        return vnow

    def finish(self) -> Dict:
        """Close the session: emit ``serve_summary`` and return it.
        Closing is one-shot — a second finish() (or one without a
        start()) raises rather than dying on an opaque TypeError."""
        s = self._sess
        if s is None:
            raise RuntimeError("serve: no open session — start() was "
                               "never called or finish() already ran")
        self._sess = None
        return self._summarize(s["completed"], s["unserved"], s["vnow"],
                               s["steps"],
                               time.perf_counter() - s["t_wall0"],
                               drained=s["draining"])

    def _kv_occupancy(self) -> Dict:
        """KV-cache occupancy of the live batch rectangle: filled token
        positions (host view of the ring fill) and the fraction of the
        cache's ``(max_batch, max_seq)`` capacity they use — the counter
        lane ``serve_trace_events`` renders."""
        if self.kv_layout is None:
            return {"kv_tokens": 0, "kv_frac": 0.0}
        ms = self.kv_layout.max_seq
        toks = sum(min(n, ms) for n in self._kv_filled)
        cap = self.max_batch * ms
        return {"kv_tokens": int(toks),
                "kv_frac": (toks / cap) if cap else 0.0}

    def _observe_request(self, req: Request) -> None:
        """Feed one completed request into the latency/TTFT histograms
        (fixed log-spaced buckets, obs/metrics.py) — the per-request
        half of the scrape, aggregatable across replicas."""
        if self.metrics is None:
            return
        if req.latency_s is not None:
            self.metrics.observe("request_latency_s", req.latency_s)
        if req.ttft_s is not None:
            self.metrics.observe("request_ttft_s", req.ttft_s)

    def _fill_kv(self, attn_ins, active, pre_lengths) -> None:
        """Project this step's NEW positions into the KV cache from the
        captured per-layer attention inputs."""
        if self.kv_cache is None:
            return
        xs = [self._to_host(x).astype(np.float32) for x in attn_ins]
        h, hd = self.kv_layout.num_heads, self.kv_layout.head_dim
        for li, (wk, wv) in enumerate(self._kv_w):
            x = xs[li]
            for slot_idx, slot in active:
                lo = self._kv_filled[slot_idx]
                hi_ = pre_lengths[slot_idx]
                if hi_ <= lo:
                    continue
                span = x[slot_idx, lo:hi_, :]          # (n, d)
                k = (span @ wk).reshape(-1, h, hd)
                v = (span @ wv).reshape(-1, h, hd)
                self.kv_cache.write_span(li, slot_idx, lo, k, v)
        for slot_idx, _ in active:
            self._kv_filled[slot_idx] = pre_lengths[slot_idx]

    # ------------------------------------------------------------------
    # forward-only service (CNN / NMT)

    def run_forward(self, requests: Sequence[Request],
                    drain: Optional[Dict] = None) -> Dict:
        """Batched forward-only service: padded fixed-shape batches
        staged through DevicePrefetcher; replies are the loss op's
        output rows.  Request meta rides host-side in FIFO order (the
        prefetcher's determinism contract), never through device
        placement."""
        from collections import deque

        from flexflow_tpu.data.prefetch import DevicePrefetcher

        t_wall0 = time.perf_counter()
        model = self.model
        in0 = model._inputs[0]
        sample_shape = tuple(in0.shape[1:])
        ordered = sorted(requests, key=lambda r: (r.arrival_v, r.rid))
        unserved: List[Request] = []
        if drain is not None and drain.get("requested"):
            ordered, unserved = [], list(ordered)
        meta: deque = deque()

        def arrays():
            for batch, members in batch_requests(
                    iter(ordered), self.max_batch,
                    pad_shape=sample_shape, dtype=in0.dtype):
                meta.append(members)
                yield batch

        predict = model.make_predict_step()
        extra = self._zero_extra_inputs()
        completed: List[Request] = []
        vnow = 0.0
        batches = 0
        with DevicePrefetcher(arrays(), machine=model.machine,
                              olog=self.olog) as pf:
            for batch in pf:
                members = meta.popleft()
                vstart = max(vnow,
                             max(r.arrival_v for r in members))
                t0 = time.perf_counter()
                out = np.asarray(predict(self.params, self.state,
                                         batch, *extra)[0])
                wall = time.perf_counter() - t0
                vnow = vstart + self.step_time_s
                batches += 1
                for i, req in enumerate(members):
                    req.admit_v = vstart
                    # a forward-only reply IS the first (and only)
                    # "token": TTFT == total latency, no decode tail
                    req.first_token_v = vnow
                    req.done_v = vnow
                    req.wall_s = wall
                    req.reply = out[i]
                    completed.append(req)
                    self._observe_request(req)
                    self.olog.event(
                        "serve_request", rid=req.rid,
                        arrival_v=req.arrival_v, admit_v=req.admit_v,
                        first_token_v=req.first_token_v,
                        done_v=req.done_v, latency_s=req.latency_s,
                        ttft_s=req.ttft_s, tpot_s=req.tpot_s,
                        prompt_len=int(np.asarray(req.tokens).shape[0])
                        if np.asarray(req.tokens).ndim else 0,
                        new_tokens=0, wall_s=wall)
                self.olog.event("serve_batch", step=batches, vnow=vnow,
                                active=len(members), admitted=len(members),
                                queue_depth=0,
                                devices=model.machine.num_devices,
                                kv_tokens=0, kv_frac=0.0)
        return self._summarize(completed, unserved, vnow, batches,
                               time.perf_counter() - t_wall0,
                               drained=bool(unserved))

    # ------------------------------------------------------------------
    # autoscaling

    def _resize(self, direction: str, step: int, vnow: float,
                depth: int, idle_streak: int) -> None:
        """One autoscale event through the elastic primitives: gather the
        live (params, state), resize the machine, re-search under the
        serving objective, rebuild, regrid — then recompile the predict
        step and reset the KV cache to the new layout."""
        import copy

        from flexflow_tpu.utils.elastic import (gather_state,
                                                research_strategy)

        if self.rebuild is None:
            return
        t0 = time.perf_counter()
        model = self.model
        machine = model.machine
        n_old = machine.num_devices
        cfg = model.config
        if direction == "shrink":
            target = self.shrink_to
            min_devices = max(int(getattr(cfg, "min_devices", 1) or 1), 1)
            if not (min_devices <= target < n_old):
                return
            if self.max_batch % target:
                return  # the batch rectangle must divide the new mesh
            live = list(range(target))
            parked = [machine.devices[i] for i in range(target, n_old)]
            new_machine = machine.shrink(live)
        else:
            if not self._parked:
                return
            new_machine = machine.grow(self._parked)
            parked = []
        full_p, full_s, _ = gather_state(model, self.params, self.state,
                                         None)
        t_search = time.perf_counter()
        strategy, research = research_strategy(
            cfg, self.rebuild, new_machine,
            getattr(cfg, "strategies", None), olog=self.olog,
            log=self.log,
            objective="decode" if self.phase == "decode" else "latency")
        research_s = time.perf_counter() - t_search
        final_cfg = copy.copy(cfg)
        final_cfg.strategies = strategy
        new_model = self.rebuild(final_cfg, new_machine)
        params, state, _ = new_model.place_state(full_p, full_s, {})
        self.model = new_model
        self.params, self.state = params, state
        self._parked = parked
        self._compile(carry={"params": params, "state": state})
        n_new = new_machine.num_devices
        rec = {
            "direction": direction, "from_devices": n_old,
            "to_devices": n_new, "step": step, "vnow": vnow,
            "queue_depth": depth, "idle_streak": idle_streak,
            "research_s": research_s, "research": research,
            "total_s": time.perf_counter() - t0,
        }
        self.resizes.append(rec)
        self.olog.event("serve_resize", **rec)
        self.log(f"serve: {direction} {n_old} -> {n_new} devices at step "
                 f"{step} (queue depth {depth}, idle streak "
                 f"{idle_streak}, re-search {research_s:.2f}s "
                 f"[{research['mode']}])")

    def adopt_resize(self, new_model, carry: Dict,
                     parked: Sequence = ()) -> None:
        """Adopt a COORDINATOR-directed resize performed outside the
        engine (utils/elastic.directed_resize under the latency
        objective): swap in the rebuilt model and its placed state,
        recompile the predict step and reset the KV layout.  Safe
        mid-session — the batch rectangle is unchanged and the next
        :meth:`step_once` refills in-flight slots' KV prefixes from the
        full-rectangle forward exactly like the autoscaler's own
        ``_resize`` recompile does.  The engine's watermark autoscaler
        and the coordinator must not both steer one engine: fleet jobs
        run with ``queue_hi=0`` / ``idle_boundaries=0``."""
        self.model = new_model
        self._parked = list(parked)
        self.params = carry["params"]
        self.state = carry["state"]
        self._compile(carry={"params": self.params, "state": self.state})

    # ------------------------------------------------------------------
    # reporting

    def _update_gauges(self, completed, depth, vnow) -> None:
        if self.metrics is None:
            return
        if self.pool:
            # a pooled engine writes ONLY its labeled series — two pools
            # scribbling the aggregate gauges would just flap them; the
            # router writes the fleet-wide aggregate itself.  E.g.
            # ff_serve_pool_queue_depth{pool="prefill"}.
            labels = {"pool": self.pool}
            s = self._sess
            self.metrics.update_labeled(
                "serve_pool_queue_depth", labels, depth)
            self.metrics.update_labeled(
                "serve_pool_active_slots", labels,
                s["batcher"].num_active() if s is not None else 0)
            self.metrics.update_labeled(
                "serve_pool_step_time_s", labels, self.step_time_s)
            self.metrics.update_labeled(
                "serve_pool_requests_total", labels, len(completed))
            self.metrics.write()
            return
        lat = [r.latency_s for r in completed if r.latency_s is not None]
        ttft = [r.ttft_s for r in completed if r.ttft_s is not None]
        tpot = [r.tpot_s for r in completed if r.tpot_s is not None]
        self.metrics.update(
            qps=(len(completed) / vnow) if vnow > 0 else 0.0,
            queue_depth=depth,
            latency_p50_s=_percentile(lat, 50) if lat else None,
            latency_p99_s=_percentile(lat, 99) if lat else None,
            ttft_p50_s=_percentile(ttft, 50) if ttft else None,
            ttft_p99_s=_percentile(ttft, 99) if ttft else None,
            tpot_p50_s=_percentile(tpot, 50) if tpot else None,
            requests_total=len(completed))
        self.metrics.write()

    def _summarize(self, completed, unserved, vnow, steps, wall_s,
                   drained=False) -> Dict:
        lat = [r.latency_s for r in completed if r.latency_s is not None]
        ttft = [r.ttft_s for r in completed if r.ttft_s is not None]
        tpot = [r.tpot_s for r in completed if r.tpot_s is not None]
        summary = {
            "requests": len(completed) + len(unserved),
            "completed": len(completed),
            "unserved": len(unserved),
            "dropped": 0,
            "qps": (len(completed) / vnow) if vnow > 0 else 0.0,
            "p50_s": _percentile(lat, 50),
            "p99_s": _percentile(lat, 99),
            "ttft_p50_s": _percentile(ttft, 50),
            "ttft_p99_s": _percentile(ttft, 99),
            "tpot_p50_s": _percentile(tpot, 50),
            "tpot_p99_s": _percentile(tpot, 99),
            "steps": steps,
            "resizes": len(self.resizes),
            "virtual_s": vnow,
            "wall_s": wall_s,
            "drained": bool(drained),
            "devices": self.model.machine.num_devices,
            "pool": self.pool,
        }
        self.olog.event("serve_summary", **summary)
        self.olog.spans()
        self._update_gauges(completed, 0, vnow)
        return summary
