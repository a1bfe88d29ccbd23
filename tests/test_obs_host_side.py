"""The host's side of a run, from inside the program (PR 36): the wall
seconds of JAX's compile stages as the union of its duration events, the
step's first call as ``ff:entry.step_build`` with a span an operator and
a recomputed block while JAX traces them, every later call as
``ff:runtime.step``, the collector's pauses, the records a name's bounded
buffer has dropped, and ``kernels.traced.<kernel>`` once a trace of a
kernel's body."""

import gc
import json
import os
import threading
import time

import numpy as np
import pytest

from flexflow_tpu import obs
from flexflow_tpu.obs import spans as obs_spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def clean_aggregate():
    obs.reset()
    yield
    obs.reset()


def _records(snap, name):
    return [r for r in snap["records"] if r["name"] == name]


# --- compile.<stage>_wall_s: the union of the stage's events ---------------

@pytest.mark.parametrize("events, measure", [
    # an inner jit's trace ends first and lies inside its caller's
    ([(1.0, 2.0), (0.0, 3.0)], 3.0),
    # two traces one after the other count both
    ([(0.0, 1.0), (5.0, 6.5)], 2.5),
    # two inner ones, then the outer one that holds both, then a later one
    ([(1.0, 2.0), (3.0, 4.0), (0.5, 4.5), (10.0, 11.0)], 5.0),
    # an event that began inside an earlier one and outlasted it
    ([(0.0, 2.0), (1.0, 3.0)], 3.0),
    ([(0.0, 2.0), (2.0, 3.0), (7.0, 7.0)], 3.0),
], ids=["nested", "disjoint", "two_inside_one", "overlapping", "touching"])
def test_wall_is_the_measure_of_the_union(events, measure):
    with obs_spans._lock:
        for a, b in events:
            got = obs_spans._wall("test.wall_s", a, b)
    assert got == pytest.approx(measure)


def test_wall_folds_its_oldest_intervals_and_clips_a_late_enclosing_one(
        monkeypatch):
    monkeypatch.setattr(obs_spans, "_WALL_INTERVALS", 8)
    with obs_spans._lock:
        for i in range(20):             # 20 disjoint intervals of 0.5
            got = obs_spans._wall("test.wall_s", i, i + 0.5)
        assert got == pytest.approx(10.0)
        assert len(obs_spans._walls["test.wall_s"][2]) <= 8
        # one that would enclose them all is clipped to where the folded
        # ones end, so nothing folded is counted twice
        frontier = obs_spans._walls["test.wall_s"][1]
        got = obs_spans._wall("test.wall_s", -1.0, 21.0)
    folded = sum(0.5 for i in range(20) if i + 0.5 <= frontier)
    assert got == pytest.approx(folded + 21.0 - frontier)


def test_nested_jits_count_once_on_the_wall_and_counter_at_answers():
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()

    @jax.jit
    def inner(x):
        return jnp.tanh(x) * 5.0 - x

    @jax.jit
    def outer(x):                       # traces ``inner`` inside its own
        return inner(x) + inner(x * 2.0)

    outer(jnp.arange(11.0)).block_until_ready()
    t1 = time.perf_counter()
    snap = obs.snapshot()
    c = snap["counters"]
    for stage in ("trace", "lower", "backend"):
        wall, summed = c[f"compile.{stage}_wall_s"], c[f"compile.{stage}_s"]
        assert 0 < wall <= summed + 1e-9, stage
        assert wall <= t1 - t0
    # the inner trace lies inside the outer one: the sum holds it twice
    assert c["compile.trace_wall_s"] < c["compile.trace_s"]
    assert obs.counter_at(snap, "compile.trace_wall_s", t0) == 0
    assert obs.counter_at(snap, "compile.trace_wall_s", t1) == \
        c["compile.trace_wall_s"]


# --- the step's first call, and every later one ----------------------------

@pytest.fixture(scope="module")
def alexnet(machine1):
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.alexnet import build_alexnet

    cfg = FFConfig(batch_size=2, input_height=64, input_width=64,
                   num_classes=10)
    ff = build_alexnet(cfg, machine1)
    images = np.zeros((2, 64, 64, 3), np.float32)
    labels = np.zeros((2,), np.int32)
    return ff, images, labels


def _state(ff):
    params, state = ff.init(3)
    return params, state, ff.init_opt_state(params)


def test_first_call_is_one_step_build_with_a_child_an_operator(alexnet):
    import jax

    ff, images, labels = alexnet
    step = ff.make_train_step()
    params, state, opt = _state(ff)
    obs.reset()
    out = step(params, state, opt, images, labels)
    jax.block_until_ready(out)
    snap = obs.snapshot()
    (build,) = _records(snap, "ff:entry.step_build")
    assert build["args"]["ops"] == len(ff.layers)
    assert build["args"]["blocks"] == 0
    ops = _records(snap, "ff:entry.trace_op")
    assert {r["args"]["op"] for r in ops} == {op.name for op in ff.layers}
    by_name = {op.name: type(op).__name__ for op in ff.layers}
    for r in ops:
        assert r["parent"] == "ff:entry.step_build"
        assert r["args"]["kind"] == by_name[r["args"]["op"]]
        assert "block" not in r["args"]
        assert build["start"] <= r["start"] <= r["end"] <= build["end"]
    # what JAX did outside any operator's Python is the parent's own
    # time (the graph plan, made on the way, is a child as well)
    total = build["end"] - build["start"]
    plans = _records(snap, "ff:entry.graph_plan")
    assert {r["parent"] for r in plans} == {"ff:entry.step_build"}
    assert sum(r["self_s"] for r in ops + plans) + build["self_s"] == \
        pytest.approx(total, rel=1e-6)
    # the sums a class are aggregates of their own (records are bounded)
    classes = {k[len("entry.trace_op_s."):]: v
               for k, v in snap["counters"].items()
               if k.startswith("entry.trace_op_s.")}
    assert set(classes) == set(by_name.values())
    assert sum(classes.values()) == pytest.approx(
        sum(r["self_s"] for r in ops), rel=1e-6)
    # the stages' wall seconds inside the span, from the union counters
    parts = [build["args"][s + "_s"] for s in
             ("trace", "lower", "backend", "cache_fetch")]
    assert build["args"]["trace_s"] > 0 and build["args"]["lower_s"] > 0
    assert sum(parts) <= total * 1.02
    assert sum(r["self_s"] for r in ops) <= build["args"]["trace_s"] * 1.02
    assert "ff:runtime.step" not in snap["spans"]


def test_later_calls_are_runtime_step_spans_numbered_from_one(alexnet):
    ff, images, labels = alexnet
    step = ff.make_train_step()
    params, state, opt = _state(ff)
    params, state, opt, _ = step(params, state, opt, images, labels)
    obs.reset()
    for _ in range(3):
        params, state, opt, loss = step(params, state, opt, images, labels)
    loss.block_until_ready()
    snap = obs.snapshot()
    assert [r["args"]["n"] for r in _records(snap, "ff:runtime.step")] \
        == [1, 2, 3]
    assert "ff:entry.step_build" not in snap["spans"]
    assert "ff:entry.trace_op" not in snap["spans"]   # nothing is traced


def test_a_call_with_tracers_and_an_eager_apply_record_nothing(alexnet):
    import jax

    ff, images, labels = alexnet
    step = ff.make_train_step()
    params, state, opt = _state(ff)
    obs.reset()
    jax.eval_shape(lambda *a: step(*a), params, state, opt, images, labels)
    snap = obs.snapshot()
    assert step.first_call is None          # inside a trace: not a run
    assert "ff:entry.step_build" not in snap["spans"]
    assert "ff:runtime.step" not in snap["spans"]
    # the operators were traced, under no step's span
    assert all(r["parent"] is None
               for r in _records(snap, "ff:entry.trace_op"))
    obs.reset()
    ff.apply(params, state, {ff._inputs[0].tid: images}, False)
    assert "ff:entry.trace_op" not in obs.snapshot()["spans"]


@pytest.fixture(scope="module")
def recomputed(machine1):
    from flexflow_tpu.models.hybrid_ssm import HybridSSMConfig, HybridSSMLM

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite_4_0_h_micro.json")) as f:
        config = json.load(f)
    config.update(config["rehearsal"])
    t = HybridSSMConfig.from_config(config, batch_size=2, seq_length=20)
    return HybridSSMLM(t, machine1)


def test_a_recomputed_block_is_one_trace_block_span(recomputed):
    ff = recomputed
    step = ff.make_train_step()
    params, state, opt = _state(ff)
    tokens = np.zeros((2, 20), np.int32)
    obs.reset()
    step(params, state, opt, tokens, tokens)[3].block_until_ready()
    snap = obs.snapshot()
    (build,) = _records(snap, "ff:entry.step_build")
    n = len(ff.recompute_blocks)
    assert build["args"]["blocks"] == n == 3
    blocks = _records(snap, "ff:entry.trace_block")
    assert [r["args"]["block"] for r in blocks] == list(range(n))
    assert [r["args"]["ops"] for r in blocks] == \
        [len(b) for b in ff.recompute_blocks]
    ops = _records(snap, "ff:entry.trace_op")
    for b, rng in enumerate(ff.recompute_blocks):
        inside = [r["args"]["op"] for r in ops if r["args"].get("block") == b]
        assert inside == [ff.layers[i].name for i in rng]
        assert snap["counters"][f"entry.trace_block_s.{b}"] == \
            pytest.approx(blocks[b]["end"] - blocks[b]["start"])
    for r in ops:
        assert r["parent"] == ("ff:entry.trace_block" if "block" in r["args"]
                               else "ff:entry.step_build")
    # parent, blocks and operators: self seconds add up to the total
    plans = _records(snap, "ff:entry.graph_plan")
    own = sum(r["self_s"] for r in ops + blocks + plans) + build["self_s"]
    assert own == pytest.approx(build["end"] - build["start"], rel=1e-6)


# --- the collector ----------------------------------------------------------

@pytest.fixture
def no_automatic_collection():
    gc.collect()
    gc.disable()
    obs.reset()
    yield
    gc.enable()


def test_an_oldest_generation_collection_is_a_span_a_young_one_a_count(
        no_automatic_collection):
    t0 = time.perf_counter()
    gc.collect()                            # the oldest generation
    snap = obs.snapshot()
    (rec,) = _records(snap, "ff:runtime.gc")
    oldest = obs_spans._OLDEST
    assert rec["args"]["generation"] == oldest and "collected" in rec["args"]
    assert t0 <= rec["start"] <= rec["end"] <= time.perf_counter()
    assert snap["counters"][f"runtime.gc_collections.gen{oldest}"] == 1
    assert snap["counters"]["runtime.gc_s"] == pytest.approx(
        rec["end"] - rec["start"])
    gc.collect(0)                           # a young one: the counter alone
    again = obs.snapshot()
    assert again["spans"]["ff:runtime.gc"]["count"] == 1
    assert again["counters"]["runtime.gc_collections.gen0"] == 1
    assert again["counters"]["runtime.gc_s"] > snap["counters"]["runtime.gc_s"]
    assert obs.counter_at(again, "runtime.gc_s", t0) == 0


def test_a_collection_inside_the_aggregates_lock_waits_to_be_published(
        no_automatic_collection):
    """The interpreter may collect between two bytecodes of a thread that
    holds the aggregate's lock: the callback must not wait for it."""
    done = threading.Event()

    def collect_under_the_lock():
        with obs_spans._lock:
            gc.collect()
        done.set()

    worker = threading.Thread(target=collect_under_the_lock, daemon=True)
    worker.start()
    worker.join(timeout=30)
    assert done.is_set(), "the collector's callback waited for the lock"
    assert len(obs_spans._gc_done) == 1     # noted, not yet published
    snap = obs.snapshot()                   # publishes what was noted
    assert snap["spans"]["ff:runtime.gc"]["count"] == 1
    assert snap["counters"]["runtime.gc_s"] > 0
    assert obs_spans._gc_done == []


# --- records say when they are short ----------------------------------------

def test_snapshot_reports_the_records_a_name_has_dropped():
    n = obs_spans.RECORDS_PER_NAME
    for i in range(n):
        with obs.span("ff:test.many", i=i):
            pass
    with obs.span("ff:test.once"):
        pass
    assert obs.snapshot()["dropped"] == {}
    for i in range(44):
        with obs.span("ff:test.many", i=n + i):
            pass
    snap = obs.snapshot()
    assert snap["dropped"] == {"ff:test.many": 44}
    assert _records(snap, "ff:test.many")[0]["args"]["i"] == 44


# --- kernels.traced.<kernel> ------------------------------------------------

def test_a_kernel_body_shared_through_traced_once_is_traced_once():
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.pallas import ssd_scan as ssd

    def traced(kernel):
        return obs.snapshot()["counters"].get("kernels.traced." + kernel, 0)

    heads, p, n, l = 8, 16, 128, 128
    rows = jax.ShapeDtypeStruct((1, 1, 1, 2 * ssd.GROUP, l), jnp.float32)
    skip = jax.ShapeDtypeStruct((1, heads * p), jnp.float32)
    xbc = jax.ShapeDtypeStruct((1, l, heads * p + 2 * n), jnp.float32)
    # past the cache: this configuration's forward and backward are
    # traced here, once each
    scan = ssd._make_scan.__wrapped__(1, 1, l, heads, p, n, "float32", True)
    assert (traced("ff_ssd_fwd"), traced("ff_ssd_bwd")) == (1, 1)

    def two_layers(rows, skip, xbc):
        loss = lambda x: (scan(rows, skip, x) + scan(rows, skip, x * 2)).sum()
        return jax.grad(loss)(xbc)

    jax.make_jaxpr(two_layers)(rows, skip, xbc)
    # two call sites and their derivatives bound the equations traced above
    assert (traced("ff_ssd_fwd"), traced("ff_ssd_bwd")) == (1, 1)

    def two_calls(rows, skip, xbc):
        call = lambda x: ssd._fwd_call(rows, skip, x, p=p, n=n,
                                       interpret=True)[0]
        return call(xbc) + call(xbc * 2)

    jax.make_jaxpr(two_calls)(rows, skip, xbc)
    assert traced("ff_ssd_fwd") == 3        # without it: one a call site
