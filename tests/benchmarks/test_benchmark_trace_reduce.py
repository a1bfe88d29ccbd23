"""``benchmarks/trace_reduce.py`` against numbers worked out by hand, on a
trace recorded on the chip (PR 24's probe) and on a hand-made one in the
same format for what one chip's trace cannot hold."""

import json
import os

import pytest

from benchmarks import trace_reduce as tr

FIX = os.path.join(os.path.dirname(__file__), "fixtures")
NS = 1e-9


def _events(name):
    with open(os.path.join(FIX, name)) as f:
        return json.load(f)["events"]


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce_events(_events("recorded_one_chip_trace.json"))


@pytest.fixture(scope="module")
def handmade():
    return tr.reduce_events(_events("handmade_two_chip_trace.json"))


# --- the recorded trace: three steps of copy-start, copy-done, fusion ----
# window = first to last device event = 71246931 - 47215677 = 24031254 ns
# busy   = 91026 + (18 + 91008) + (17 + 91010) = 273079 ns
# gaps   = 11938239 + 11819933 (between steps) + 1 + 2 (inside steps)
# spans  = bench:train_step covers 1076640 of the first long gap and
#          1012000 of the second; the third span lies after the window

RECORDED = {
    "window_s": 24031254, "busy_s": 273079, "collective_s": 0,
    "exposed_collective_s": 0,
}


@pytest.mark.parametrize("key", sorted(RECORDED))
def test_recorded_totals(recorded, key):
    assert recorded[key] == pytest.approx(RECORDED[key] * NS, rel=1e-12)


@pytest.mark.parametrize("name,ns", [
    ("bench:train_step", 1076640 + 1012000),
    (tr.OUTSIDE_SPANS, 11938239 - 1076640 + 11819933 - 1012000),
    (tr.SHORT_GAPS, 3)])
def test_recorded_gaps_by_span(recorded, name, ns):
    assert recorded["idle_gaps_s"][name] == pytest.approx(ns * NS)


def test_recorded_gaps_sum_to_idle(recorded):
    assert sum(recorded["idle_gaps_s"].values()) == pytest.approx(
        recorded["window_s"] - recorded["busy_s"])


@pytest.mark.parametrize("name,ns", [
    ("fusion|fusion", 91009 + 91008 + 91010),
    ("copy-start|copy-start", 13 * 3), ("copy-done|copy-done", 2 + 3 + 2)])
def test_recorded_op_seconds(recorded, name, ns):
    assert recorded["op_s"][name] == pytest.approx(ns * NS)


def test_recorded_has_one_device_and_no_modules_line(recorded):
    assert recorded["devices"] == 1
    assert not [k for k in recorded["op_s"] if k.startswith("jit_")]


# --- the hand-made trace: two devices, window [0, 500000) ns ---------------
# device 0: busy [0,180000) + [200000,262000) (async all-reduce) +
#           [300000,400000) = 342000; collectives [150000,180000) +
#           [200000,262000) = 92000, of which no other op ran during
#           30000 + 1000 + 21000 = 52000
# device 1: busy [0,180000); collective [160000,180000), all exposed
# gaps, device 0: [180000,200000) [262000,300000) [400000,500000)
#       device 1: [180000,500000)
# spans: train_step [0,190000), fence [190000,450000), next_batch nested
#        in fence at [270000,290000)

HANDMADE = {
    "window_s": 500000, "busy_s": (342000 + 180000) / 2,
    "collective_s": (92000 + 20000) / 2,
    "exposed_collective_s": (52000 + 20000) / 2,
}


@pytest.mark.parametrize("key", sorted(HANDMADE))
def test_handmade_totals(handmade, key):
    assert handmade[key] == pytest.approx(HANDMADE[key] * NS, rel=1e-12)


@pytest.mark.parametrize("name,ns", [
    ("bench:train_step", (10000 + 10000) / 2),
    ("bench:fence", (78000 + 240000) / 2),
    ("bench:next_batch", (20000 + 20000) / 2),
    (tr.OUTSIDE_SPANS, (50000 + 50000) / 2)])
def test_handmade_gaps_go_to_the_innermost_span(handmade, name, ns):
    assert handmade["idle_gaps_s"][name] == pytest.approx(ns * NS)


@pytest.mark.parametrize("name,ns", [
    ("fusion.1|fusion", 100000), ("ff_flash_fwd.3|custom-call", 55000),
    ("all-reduce.1|all-reduce", 25000),
    ("all-reduce-start.2|all-reduce-start", 500),
    ("all-reduce-done.2|all-reduce-done", 1000),
    ("while.5|while", (100000 - 40000 - 40000) / 2),   # self time
    ("transpose_jvp_ff_maxpool_bwd_.6|custom-call", 20000), ("fusion.7|fusion", 20000)])
def test_handmade_op_self_seconds(handmade, name, ns):
    assert handmade["op_s"][name] == pytest.approx(ns * NS)


@pytest.mark.parametrize("prefix,ns", [
    ("ff_", 75000),            # ff_flash_fwd.3 and transpose_jvp_ff_maxpool_..
    ("ff_flash_", 55000), ("ff_maxpool_", 20000), ("ff_ce_", 0),
    ("f_", 0)])                # a kernel's name starts at a word
def test_kernel_sums(handmade, prefix, ns):
    assert tr.kernel_seconds(handmade, prefix) == pytest.approx(ns * NS)


def test_breakdown_is_the_contracts(handmade):
    b = tr.breakdown(handmade, top=3)
    assert [n for n, _ in b["device_ops"]] == [
        "fusion.1__fusion", "ff_flash_fwd.3__custom-call",
        "all-reduce.1__all-reduce"]
    assert b["idle_gaps"][0][0] == "bench:fence"
    assert all(len(v) <= 3 for v in b.values())


def test_no_device_plane_reduces_to_nothing():
    host_only = [e for e in _events("handmade_two_chip_trace.json")
                 if not e["plane"].startswith("/device:")]
    assert tr.reduce_events(host_only) is None


@pytest.mark.parametrize("name,want", [
    ("%fusion.2 = bf16[8,128]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[8]{0} %p), "
     "kind=kOutput, calls=%fc", ("fusion.2", "fusion")),
    ("%copy-start = (bf16[2048]{0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) "
     "copy-start(bf16[2048]{0} %a.1)", ("copy-start", "copy-start")),
    ("%ff_flash_bwd_dq.7 = bf16[1]{0} custom-call(bf16[1]{0} %q), "
     "custom_call_target=\"tpu_custom_call\"",
     ("ff_flash_bwd_dq.7", "custom-call")),
    ("all-reduce.12", ("all-reduce.12", "all-reduce"))])
def test_split_name(name, want):
    assert tr.split_name(name) == want


@pytest.mark.parametrize("opcode,want", [
    ("all-reduce", True), ("all-reduce-start", True),
    ("collective-permute-done", True), ("all-gather", True),
    ("fusion", False), ("copy-start", False), ("custom-call", False)])
def test_is_collective(opcode, want):
    assert tr.is_collective(opcode) is want


def test_interval_arithmetic():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 9), (4, 4)])
    assert u == [(0, 3), (5, 9)]
    assert tr.measure(u) == 7
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
