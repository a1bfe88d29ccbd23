"""obs.span / obs.count (flexflow_tpu/obs/spans.py): the one process-global
span and counter API — nesting and self time, threads, the bounded record
buffer, counters and their history, the ``spans`` obs record, the
profiler's clock, JAX's compile events, and the call sites that measure
with it (``DevicePrefetcher.stall_s``, ``ServeEngine.step_once``)."""

import glob
import itertools
import sys
import threading
import time

import numpy as np
import pytest

from flexflow_tpu import obs
from flexflow_tpu.obs import spans as obs_spans


@pytest.fixture(autouse=True)
def clean_aggregate():
    obs.reset()
    yield
    obs.reset()


def _but_gc(d):
    """``d`` without what the collector's callback adds whenever Python
    collects (PR 36: ``runtime.gc_*``, ``ff:runtime.gc``), which may be
    between any two statements of a test."""
    return {k: v for k, v in d.items()
            if not k.startswith(("runtime.gc_", "ff:runtime.gc"))}


def test_nested_spans_record_parent_and_self_time():
    with obs.span("ff:test.outer", step=3) as outer:
        time.sleep(0.01)
        with obs.span("ff:test.inner") as inner:
            time.sleep(0.02)
    snap = obs.snapshot()
    o, i = snap["spans"]["ff:test.outer"], snap["spans"]["ff:test.inner"]
    assert o["count"] == i["count"] == 1
    assert o["total_s"] == pytest.approx(outer.seconds)
    assert i["total_s"] == i["self_s"] == pytest.approx(inner.seconds)
    # self time: the duration less what child spans cover
    assert o["self_s"] == pytest.approx(outer.seconds - inner.seconds)
    assert 0.009 < o["self_s"] < outer.seconds
    recs = {r["name"]: r for r in snap["records"]}
    assert recs["ff:test.inner"]["parent"] == "ff:test.outer"
    assert recs["ff:test.outer"]["parent"] is None
    assert recs["ff:test.outer"]["args"] == {"step": 3}
    assert recs["ff:test.outer"]["start"] <= recs["ff:test.inner"]["start"]
    assert recs["ff:test.inner"]["end"] <= recs["ff:test.outer"]["end"]


def test_args_added_inside_the_block_reach_the_record():
    with obs.span("ff:test.late", batch=1) as sp:
        sp.args["bytes"] = 4096
    (rec,) = obs.snapshot()["records"]
    assert rec["args"] == {"batch": 1, "bytes": 4096}


def test_a_span_closes_and_counts_when_its_block_raises():
    with pytest.raises(KeyError):
        with obs.span("ff:test.raises"):
            raise KeyError("x")
    assert obs.snapshot()["spans"]["ff:test.raises"]["count"] == 1
    with obs.span("ff:test.after"):
        pass
    # the failed span did not stay on the thread's stack
    assert obs.snapshot()["records"][-1]["parent"] is None


def test_spans_from_two_threads_keep_their_own_parents():
    ready = threading.Barrier(2, timeout=10)

    def worker(tag):
        with obs.span(f"ff:test.{tag}"):
            ready.wait()            # both outer spans are open at once
            with obs.span("ff:test.child", tag=tag):
                time.sleep(0.005)

    threads = [threading.Thread(target=worker, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    snap = obs.snapshot()
    assert snap["spans"]["ff:test.child"]["count"] == 2
    children = [r for r in snap["records"] if r["name"] == "ff:test.child"]
    assert {r["parent"] for r in children} == {"ff:test.a", "ff:test.b"}
    for r in children:
        assert r["parent"] == f"ff:test.{r['args']['tag']}"
    assert len({r["thread"] for r in children}) == 2


def test_concurrent_spans_and_counts_lose_no_update():
    """More workers than cores, a short switch interval: every span and
    every count lands in the aggregate."""
    workers, each = 16, 300
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def work():
            for i in range(each):
                with obs.span("ff:test.stress", i=i):
                    obs.count("test.stress")

        threads = [threading.Thread(target=work) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    snap = obs.snapshot()
    assert snap["spans"]["ff:test.stress"]["count"] == workers * each
    assert snap["counters"]["test.stress"] == workers * each


def test_the_record_buffer_is_bounded_per_name_and_the_aggregate_is_not():
    n = obs_spans.RECORDS_PER_NAME + 50
    with obs.span("ff:test.once"):
        pass
    for i in range(n):
        with obs.span("ff:test.many", i=i):
            pass
    snap = obs.snapshot()
    assert snap["spans"]["ff:test.many"]["count"] == n
    many = [r for r in snap["records"] if r["name"] == "ff:test.many"]
    assert len(many) == obs_spans.RECORDS_PER_NAME
    assert many[-1]["args"]["i"] == n - 1          # the newest are kept
    # a rare span's record is not pushed out by a frequent one
    assert any(r["name"] == "ff:test.once" for r in snap["records"])


def test_count_and_its_history():
    t0 = time.perf_counter()
    obs.count("test.bytes", 10)
    obs.count("test.bytes", 5)
    obs.count("test.events")
    t1 = time.perf_counter()
    time.sleep(obs_spans._HISTORY_BUCKET_S + 0.05)
    obs.count("test.bytes", 100)
    snap = obs.snapshot()
    assert _but_gc(snap["counters"]) == {"test.bytes": 115,
                                         "test.events": 1}
    assert obs.counter_at(snap, "test.bytes", t0) == 0
    assert obs.counter_at(snap, "test.bytes", t1) == 15
    assert obs.counter_at(snap, "test.bytes", time.perf_counter()) == 115
    assert obs.counter_at(snap, "test.never", t1) == 0


def test_snapshot_is_a_copy_and_reset_clears():
    with obs.span("ff:test.kept"):
        obs.count("test.kept", 2)
    snap = obs.snapshot()
    snap["spans"].clear()
    snap["counters"]["test.kept"] = 99
    again = obs.snapshot()
    assert again["spans"]["ff:test.kept"]["count"] == 1
    assert again["counters"]["test.kept"] == 2
    obs.reset()
    empty = obs.snapshot()
    assert _but_gc(empty["spans"]) == {}
    assert _but_gc(empty["counters"]) == {}
    assert [r for r in empty["records"]
            if r["name"] != "ff:runtime.gc"] == []
    assert _but_gc(empty["counter_history"]) == {}
    assert empty["dropped"] == {}


def test_runlog_timer_lands_in_the_aggregate(tmp_path):
    olog = obs.RunLog(str(tmp_path / "t.jsonl"), surface="test")
    with olog.timer("slept", why="test"):
        time.sleep(0.01)
    olog.close()
    agg = obs.snapshot()["spans"]["ff:timer.slept"]
    (rec,) = [e for e in obs.read_events(olog.path) if e["kind"] == "timer"]
    assert rec["name"] == "slept" and rec["why"] == "test"
    assert agg["count"] == 1
    assert rec["seconds"] == pytest.approx(agg["total_s"]) and \
        rec["seconds"] >= 0.01


def test_one_spans_record_and_the_report_renders_it(tmp_path):
    from flexflow_tpu.obs import report

    olog = obs.RunLog(str(tmp_path / "s.jsonl"), surface="test")
    with obs.span("ff:test.reported"):
        obs.count("test.reported", 7)
    olog.spans()
    olog.close()
    obs.NULL.spans()            # the disabled sink takes the call too
    events = list(obs.read_events(olog.path))
    (rec,) = [e for e in events if e["kind"] == "spans"]
    assert rec["spans"]["ff:test.reported"]["count"] == 1
    assert rec["counters"]["test.reported"] == 7
    assert "records" not in rec         # the aggregate, not the raw spans
    text = report.render(events)
    assert "== spans" in text and "ff:test.reported" in text
    assert "counter test.reported: 7" in text
    assert report.summarize(events)["kinds"]["spans"] == 1


def test_a_span_lies_in_the_profilers_trace_with_its_args(tmp_path):
    """Under a profiler session the span is a TraceMe of the .xplane.pb,
    on the clock of the device events, its args the event's stats."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.span("ff:test.traced", step=3, what="x"):
            with obs.span("ff:test.traced_child"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("ff:test."):
                    found[e.name] = (e.start_ns, e.duration_ns,
                                     {k: str(v) for k, v in e.stats})
    outer, child = found["ff:test.traced"], found["ff:test.traced_child"]
    assert outer[2] == {"step": "3", "what": "x"}
    assert outer[0] <= child[0]
    assert child[0] + child[1] <= outer[0] + outer[1]
    assert child[1] >= 2e6      # nanoseconds
    # and with no session the same span costs microseconds, not a trace
    t0 = time.perf_counter()
    for i in range(2000):
        with obs.span("ff:test.dormant", step=i):
            pass
    assert (time.perf_counter() - t0) / 2000 < 2e-4


def test_compile_counters_move_when_a_function_is_jitted():
    import jax
    import jax.numpy as jnp

    before = obs.snapshot()["counters"]

    @jax.jit
    def fresh(x):                      # a function nothing compiled yet
        return jnp.tanh(x) * 3.0 + x

    fresh(jnp.arange(7.0)).block_until_ready()
    after = obs.snapshot()["counters"]
    for name in ("compile.trace_s", "compile.lower_s", "compile.backend_s"):
        assert after.get(name, 0.0) > before.get(name, 0.0), name
    steady = obs.snapshot()["counters"]
    fresh(jnp.arange(7.0)).block_until_ready()     # cached: nothing moves
    assert _but_gc(obs.snapshot()["counters"]) == _but_gc(steady)


def test_prefetcher_stall_equals_the_wait_spans_total(machine1):
    from flexflow_tpu.data.prefetch import DevicePrefetcher

    def slow():
        for i in range(5):
            time.sleep(0.01)
            yield (np.full((4, 3), i, np.float32),
                   np.full((4,), i, np.int32))

    with DevicePrefetcher(slow(), machine=machine1, depth=2) as data:
        got = [int(b[1][0]) for b in data]
    assert got == [0, 1, 2, 3, 4]
    snap = obs.snapshot()
    wait, put = (snap["spans"]["ff:runtime.prefetch_wait"],
                 snap["spans"]["ff:runtime.prefetch_put"])
    assert data.stall_s == pytest.approx(wait["total_s"], rel=1e-12)
    assert data.stall_s > 0.0          # the consumer outran the source
    assert wait["count"] == 6          # five batches and the end
    assert put["count"] == 5
    assert snap["counters"]["runtime.prefetch_bytes"] == 5 * (4 * 3 * 4 + 16)
    puts = [r for r in snap["records"]
            if r["name"] == "ff:runtime.prefetch_put"]
    assert [r["args"]["batch"] for r in puts] == [0, 1, 2, 3, 4]
    assert all(r["args"]["bytes"] == 64 for r in puts)
    waits = [r for r in snap["records"]
             if r["name"] == "ff:runtime.prefetch_wait"]
    # the worker's spans come from another thread than the consumer's
    assert {r["thread"] for r in puts}.isdisjoint(
        {r["thread"] for r in waits})


def test_prefetcher_passes_placed_batches_through_without_a_put(machine1):
    import jax

    from flexflow_tpu.data.prefetch import DevicePrefetcher

    placed = (jax.device_put(np.ones((2, 2), np.float32),
                             machine1.devices[0]),)
    with DevicePrefetcher(itertools.repeat(placed), machine=machine1) as d:
        next(d)
    assert obs.snapshot()["counters"]["runtime.prefetch_bytes"] == 0


@pytest.fixture(scope="module")
def tiny_lm(machine8):
    from flexflow_tpu.apps.serve import _build_lm

    return _build_lm(machine8, batch=8, seed=0, tiny=True,
                     research_budget_s=0.5)


def test_serve_step_emits_its_span_its_children_and_the_bytes_copied(
        tiny_lm):
    from flexflow_tpu.serve.engine import ServeEngine
    from flexflow_tpu.serve.loadgen import synthetic_requests

    model, _ = tiny_lm
    eng = ServeEngine(model, None, log=lambda *a: None)
    reqs = synthetic_requests(3, seed=4, rate_qps=1000.0, vocab_size=64,
                              prompt_len=3, max_new_tokens=2)
    for r in reqs:
        r.arrival_v = 0.0
    eng.start(reqs)
    obs.reset()                       # the engine's set-up is not a step
    assert eng.step_once()
    snap = obs.snapshot()
    children = ("ff:serve.forward", "ff:serve.to_host", "ff:serve.sample",
                "ff:serve.kv_fill")
    assert snap["spans"]["ff:serve.step"]["count"] == 1
    (step,) = [r for r in snap["records"] if r["name"] == "ff:serve.step"]
    assert step["args"] == {"step": 1, "active": 3}
    for name in children:
        (rec,) = [r for r in snap["records"] if r["name"] == name]
        assert rec["parent"] == "ff:serve.step", name
        assert step["start"] <= rec["start"] and rec["end"] <= step["end"]
    covered = sum(snap["spans"][c]["total_s"] for c in children)
    assert snap["spans"]["ff:serve.step"]["self_s"] == pytest.approx(
        snap["spans"]["ff:serve.step"]["total_s"] - covered)
    # every array the step copied to the host: the log-probs and one
    # attention input a layer, each (max_batch, max_len, ...) float32
    tokens = np.zeros((eng.max_batch, eng.max_len), np.int32)
    outs = eng._predict(eng.params, eng.state, tokens,
                        *eng._zero_extra_inputs())
    assert snap["counters"]["serve.host_bytes"] == sum(
        int(np.asarray(o).nbytes) for o in outs)
    assert snap["counters"]["serve.tokens_out"] == 3
    while eng.step_once():
        pass
    assert obs.snapshot()["spans"]["ff:serve.step"]["count"] \
        == eng.session_steps() == 2
    eng.finish()
    assert all(len(r.reply) == 2 for r in reqs)


def test_entry_spans_around_the_train_state(machine1):
    """abstract_train_state, init under a caller's jit, init_opt_state
    and the graph plan each leave their span, nested as they ran."""
    import jax

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.alexnet import build_alexnet

    cfg = FFConfig(batch_size=2, input_height=64, input_width=64,
                   num_classes=10)
    ff = build_alexnet(cfg, machine1)
    ff.abstract_train_state()

    def fresh(seed):
        params, state = ff.init(seed)
        return params, state, ff.init_opt_state(params)

    jax.jit(fresh)(np.int32(1))
    ff.make_train_step()
    snap = obs.snapshot()
    assert snap["spans"]["ff:entry.abstract_state"]["count"] == 1
    assert snap["spans"]["ff:entry.init"]["count"] == 2
    assert snap["spans"]["ff:entry.opt_state"]["count"] == 2
    inits = [r for r in snap["records"] if r["name"] == "ff:entry.init"]
    assert inits[0]["parent"] == "ff:entry.abstract_state"
    assert inits[0]["args"]["abstract"] == 1 and inits[1]["parent"] is None
    assert inits[1]["args"]["ops"] == len(ff.layers)
    assert inits[1]["args"]["leaves"] == len(jax.tree.leaves(
        ff.abstract_train_state()[:2]))
    assert obs.program("train_step") is ff
