"""Whole-graph regrid planner: producer->consumer resharding resolved ONCE.

Before this module, FFModel._apply re-derived every producer->consumer
reshard edge-by-edge on EVERY trace (``machine.global_entries`` +
``machine.regrid_steps`` per input, per op) and each consumer of a fanned-
out producer traced its own identical constraint chain.  GSPMD's
observation (Xu et al., 2021) is that resharding *placement* — not just op
partitioning — decides whether a mixed strategy wins; FlexFlow leans on
Legion to make these transfers implicit and cheap (conv_2d.cu:171-208).
The planner is the executor-side analog of the simulator's memoized
transfer plans (PR 2): walk the op graph once at plan time and produce a
per-edge :class:`EdgePlan`, so ``_apply`` becomes a thin consumer.

What planning buys over the per-trace path:

  * **resolved once** — source/target global-mesh entries and the hop
    decomposition are computed at plan time, never inside the traced step;
  * **coalescing** — edges between consecutive ops sharing a layout are
    recognized as no-ops at plan time and carry zero constraints (the
    per-edge path pays the resolution every trace to discover the same
    thing), and identity hops are dropped;
  * **fan-out sharing** — when one producer feeds several consumers that
    want the same layout, the constraint chain is traced ONCE and the
    resharded value reused (the per-edge path emits one chain per
    consumer and hopes XLA CSEs them);
  * **cost-aware hop selection** — among alternative single-axis hop
    decompositions of one edge, a uniform-cost search picks the sequence
    the machine :class:`~flexflow_tpu.machine.Topology` prices cheapest
    (the same ICI/DCN link numbers the native simulator's memoized
    transfer plans use, keeping sim and executor aligned).  The greedy
    ``MachineModel.regrid_steps`` order gathers dropped axes FIRST, which
    prices every later all-to-all at the grown per-shard size; moving
    while still fully sharded and gathering last is often strictly
    cheaper.

Every value move here is data movement only (all-gather / all-to-all /
slice) — planned execution is loss-bit-identical to the per-trace path by
construction (tests/test_regrid_planner.py).
"""

from __future__ import annotations

import dataclasses
import heapq
import math
from typing import Dict, List, Optional, Tuple

from flexflow_tpu.machine import MachineModel, Topology
from flexflow_tpu.sim.collectives import _allreduce, _alltoall

# cost charged to a pure split hop (a slice: no wire traffic) — small and
# nonzero so the search prefers fewer hops among traffic-free plans
_SPLIT_EPS = 1.0e-7

# uniform-cost-search state cap; beyond it fall back to the greedy
# decomposition (machine sizes this repo targets stay far below the cap)
_MAX_STATES = 20000


# ---------------------------------------------------------------------------
# hop pricing on the global factored mesh


class _MeshCosts:
    """Link-cost oracle for hops on one machine's global factored mesh.

    Caches, per global-mesh axis subset, the device tuple of the axis
    group containing device 0 (translates share the tier pattern when the
    ICI group size divides the machine — the layout MachineModel builds),
    and prices gather/all-to-all hops with the SAME
    :mod:`flexflow_tpu.sim.collectives` ring formulas the simulator uses.
    """

    def __init__(self, machine: MachineModel):
        self.topo: Topology = machine.topology
        fac = machine.global_factors()
        self.sizes = {name: s for name, s in fac}
        strides: Dict[str, int] = {}
        stride = 1
        for name, s in reversed(fac):
            strides[name] = stride
            stride *= s
        self.strides = strides
        self._groups: Dict[Tuple[str, ...], Tuple[int, ...]] = {}

    def group(self, axes: Tuple[str, ...]) -> Tuple[int, ...]:
        """Device ordinals of the axis-``axes`` collective group holding
        device 0 (the representative group pricing the hop)."""
        key = tuple(sorted(axes))
        devs = self._groups.get(key)
        if devs is None:
            devs = (0,)
            for a in key:
                stride, size = self.strides[a], self.sizes[a]
                devs = tuple(d + i * stride for d in devs
                             for i in range(size))
            devs = tuple(sorted(devs))
            self._groups[key] = devs
        return devs

    def nshards(self, state: Tuple[Tuple[str, ...], ...]) -> int:
        n = 1
        for t in state:
            for a in t:
                n *= self.sizes[a]
        return n

    def alltoall(self, per_shard_bytes: float, axis: str) -> float:
        return _alltoall(per_shard_bytes, self.group((axis,)), self.topo)

    def allgather(self, per_shard_bytes_after: float,
                  axes: Tuple[str, ...]) -> float:
        # an all-gather is half an all-reduce of the gathered volume (the
        # dispatch_overhead_cost convention in sim/collectives.py)
        return 0.5 * _allreduce(per_shard_bytes_after, self.group(axes),
                                self.topo)


def _hop_traffic(costs: _MeshCosts, total_bytes: float,
                 prev, nxt) -> Tuple[float, float]:
    """(seconds, wire_bytes) of the single hop ``prev -> nxt``; both are
    entries tuples (per-tensor-dim tuples of global mesh axes)."""
    prev_axes = [a for t in prev for a in t]
    nxt_axes = [a for t in nxt for a in t]
    removed = tuple(a for a in prev_axes if a not in nxt_axes)
    added = [a for a in nxt_axes if a not in prev_axes]
    per_prev = total_bytes / max(costs.nshards(prev), 1)
    per_nxt = total_bytes / max(costs.nshards(nxt), 1)
    if removed and not added:
        # gather: each shard ends holding the grown block
        p = len(costs.group(removed))
        return (costs.allgather(per_nxt, removed),
                (p - 1) / max(p, 1) * total_bytes)
    if not removed and not added:
        # a move within/between tensor dims: one all-to-all over the moved
        # axis (exactly one axis changes location per hop)
        moved = None
        for a in prev_axes:
            loc_prev = next((j, t.index(a)) for j, t in enumerate(prev)
                            if a in t)
            loc_nxt = next((j, t.index(a)) for j, t in enumerate(nxt)
                           if a in t)
            if loc_prev != loc_nxt:
                moved = a
                break
        if moved is None:
            return 0.0, 0.0
        s = costs.sizes[moved]
        return (costs.alltoall(per_prev, moved),
                (s - 1) / s * total_bytes)
    if added and not removed:
        return _SPLIT_EPS, 0.0  # pure split: a local slice
    # mixed (should not be produced by the planner's move set): price as
    # gather + split, conservatively
    p = len(costs.group(removed))
    return (costs.allgather(per_nxt, removed),
            (p - 1) / max(p, 1) * total_bytes)


def price_chain(machine: MachineModel, src, chain: List,
                shape: Tuple[int, ...], itemsize: int = 4,
                costs: Optional[_MeshCosts] = None) -> Tuple[float, float]:
    """(seconds, wire_bytes) of walking ``src`` through ``chain`` (a list
    of entries tuples ending at the destination)."""
    costs = costs or _MeshCosts(machine)
    total = float(math.prod(shape)) * itemsize
    secs = moved = 0.0
    cur = src
    for step in chain:
        s, b = _hop_traffic(costs, total, cur, step)
        secs += s
        moved += b
        cur = step
    return secs, moved


# ---------------------------------------------------------------------------
# cost-aware hop selection


def _correct_prefix_len(cur_j, dst_j) -> int:
    n = 0
    for a, b in zip(cur_j, dst_j):
        if a != b:
            break
        n += 1
    return n


def plan_hops(machine: MachineModel, src, dst,
              shape: Tuple[int, ...], itemsize: int = 4,
              costs: Optional[_MeshCosts] = None):
    """Min-cost single-axis hop decomposition of the regrid ``src -> dst``
    (both entries tuples of equal rank): a uniform-cost search over states
    whose moves are the same vocabulary ``MachineModel.regrid_steps``
    emits — merged or single all-gathers (axis drops), all-to-alls (axis
    moves onto a ready destination prefix) and slices (axis splits) —
    priced with the machine topology's link costs.  Returns
    ``(chain, seconds, wire_bytes)`` where ``chain`` is the list of
    intermediate entries tuples INCLUDING ``dst`` as its last element
    (empty when ``src == dst``), or the greedy decomposition when the
    search exceeds its state budget.  Unlike the greedy, the search always
    reaches ``dst`` (a misplaced axis can be gathered and re-split), so
    it never returns None."""
    if len(src) != len(dst):
        raise ValueError(f"rank mismatch: {src} vs {dst}")
    if src == dst:
        return [], 0.0, 0.0
    costs = costs or _MeshCosts(machine)
    total = float(math.prod(shape)) * itemsize
    dst_axes = {a for t in dst for a in t}
    src_t = tuple(tuple(t) for t in src)
    dst_t = tuple(tuple(t) for t in dst)

    def neighbors(state):
        cur = [list(t) for t in state]
        loc = {a: j for j, t in enumerate(cur) for a in t}
        out = []
        # merged gather of every axis absent from dst (the greedy's first
        # hop) plus single gathers of misplaced axes
        foreign = [a for t in cur for a in t if a not in dst_axes]
        if foreign:
            out.append(tuple(tuple(a for a in t if a in dst_axes)
                             for t in cur))
        for j, t in enumerate(cur):
            keep = _correct_prefix_len(t, dst_t[j])
            for i, a in enumerate(t):
                if i >= keep and (a in dst_axes or len(foreign) > 1):
                    nxt = [list(x) for x in cur]
                    nxt[j].remove(a)
                    out.append(tuple(tuple(x) for x in nxt))
        # moves / splits building each destination prefix
        for j, t in enumerate(cur):
            p = len(t)
            if p < len(dst_t[j]) and tuple(t) == dst_t[j][:p]:
                a = dst_t[j][p]
                nxt = [list(x) for x in cur]
                if a in loc:
                    nxt[loc[a]].remove(a)
                nxt[j].append(a)
                out.append(tuple(tuple(x) for x in nxt))
        return out

    frontier = [(0.0, 0, src_t, None)]
    best: Dict = {}
    parents: Dict = {}
    order = 0
    explored = 0
    while frontier:
        cost, _, state, parent = heapq.heappop(frontier)
        if state in best and best[state] <= cost:
            continue
        best[state] = cost
        parents[state] = parent
        if state == dst_t:
            chain = []
            cur = state
            while cur is not None and cur != src_t:
                chain.append(cur)
                cur = parents[cur]
            chain.reverse()
            _, moved = price_chain(machine, src_t, chain, shape,
                                   itemsize, costs)
            return chain, cost, moved
        explored += 1
        if explored > _MAX_STATES:
            break
        for nxt in neighbors(state):
            if nxt == state:
                continue
            s, _ = _hop_traffic(costs, total, state, nxt)
            order += 1
            heapq.heappush(frontier, (cost + s, order, nxt, state))
    # state budget exceeded: fall back to the greedy decomposition (or
    # full replicate-and-slice when even that cannot reach dst)
    steps = machine.regrid_steps(src_t, dst_t)
    if steps is None:
        repl = tuple(() for _ in src_t)
        chain = [repl, dst_t]
    else:
        chain = list(steps) + [dst_t]
    secs, moved = price_chain(machine, src_t, chain, shape, itemsize, costs)
    return chain, secs, moved


# ---------------------------------------------------------------------------
# the plan


@dataclasses.dataclass
class EdgePlan:
    """One consumer input's resharding, resolved at plan time.

    ``shardings`` is the full constraint chain to apply in order (hops
    then destination; empty = coalesced no-op edge).  ``share_key`` is set
    when several edges of the plan reshard the same produced value to the
    same destination — the first consumer traces the chain, the rest
    reuse the traced value.

    Accounting separates the plan's two wins: ``naive_constraints``
    counts what per-edge blind resolution would emit for THIS edge (its
    chosen chain, one destination constraint even for a no-op edge),
    against which the summary's after-coalescing counts are compared;
    ``greedy_s``/``greedy_bytes`` price the greedy
    ``MachineModel.regrid_steps`` decomposition against the cost-chosen
    ``predicted_s``/``predicted_bytes``."""

    shardings: List
    share_key: Optional[Tuple] = None
    # coalescing accounting (obs record + tests)
    naive_constraints: int = 0
    constraints: int = 0
    # hop-selection accounting: chosen chain vs the greedy decomposition
    predicted_s: float = 0.0
    predicted_bytes: float = 0.0
    greedy_s: float = 0.0
    greedy_bytes: float = 0.0


class RegridPlan:
    """Per-edge reshard plans for one (model, schedule, fusion) — built
    once by :func:`build_regrid_plan`, consumed by ``FFModel._apply``."""

    def __init__(self, machine: MachineModel):
        self.machine = machine
        self.edges: Dict[Tuple[str, int], EdgePlan] = {}
        self._shared_first: set = set()

    # -- construction ----------------------------------------------------

    def add_edge(self, op_name: str, input_idx: int, src, dst,
                 shape, itemsize: int = 4,
                 replicate_unknown: bool = False,
                 costs: Optional[_MeshCosts] = None,
                 tid: Optional[int] = None) -> None:
        """Plan the edge ``src -> dst`` for ``op``'s ``input_idx``-th
        input.  ``src is None`` means the producer's layout is unknown
        (a non-decomposing placement-group exit): with
        ``replicate_unknown`` the plan states the replicate waypoint the
        legacy path used, otherwise the edge is skipped (the group-input
        convention)."""
        m = self.machine
        key = (op_name, input_idx)
        if dst is None:
            return
        if src is None:
            if not replicate_unknown:
                return
            self.edges[key] = EdgePlan(
                shardings=[m.replicated(), m.entries_sharding(dst)],
                naive_constraints=2, constraints=2)
            return
        if dst == src:
            # coalesced: consecutive ops sharing this layout need no
            # constraint at all — the naive per-edge path would still
            # constrain the input to its wanted layout (1 constraint)
            self.edges[key] = EdgePlan(shardings=[], naive_constraints=1,
                                       constraints=0)
            return
        costs = costs or _MeshCosts(m)
        greedy_steps = m.regrid_steps(src, dst)
        if greedy_steps is None:
            greedy_chain = [tuple(() for _ in src),
                            tuple(tuple(t) for t in dst)]
        else:
            greedy_chain = list(greedy_steps) + [tuple(tuple(t)
                                                       for t in dst)]
        greedy_s, greedy_b = price_chain(m, src, greedy_chain, shape,
                                         itemsize, costs)
        chain, secs, moved = plan_hops(m, src, dst, shape, itemsize, costs)
        # the share key names the PRODUCED VALUE and the destination: only
        # consumers of the same tensor wanting the same layout reuse one
        # traced chain (summary() counts sharing with the same key)
        share_key = (tid, tuple(tuple(t) for t in src),
                     tuple(tuple(t) for t in dst))
        self.edges[key] = EdgePlan(
            shardings=[m.entries_sharding(s) for s in chain],
            share_key=share_key,
            naive_constraints=len(chain), constraints=len(chain),
            predicted_s=secs, predicted_bytes=moved,
            greedy_s=greedy_s, greedy_bytes=greedy_b)

    # -- consumption (inside the traced step) ----------------------------

    def apply(self, op_name: str, input_idx: int, x, cache: Dict):
        """Apply the planned constraint chain for one edge to value ``x``.
        ``cache`` is the per-trace fan-out dict: consumers sharing a
        (produced value, destination) reuse the first traced reshard."""
        ep = self.edges.get((op_name, input_idx))
        if ep is None or not ep.shardings:
            return x
        from jax import lax

        import jax

        ck = ep.share_key
        if ck is not None and ck in cache:
            return cache[ck]
        # a fan-out reshard is traced once, under its first consumer
        with jax.named_scope(f"ff_regrid.{op_name}.{input_idx}"):
            for sh in ep.shardings:
                x = lax.with_sharding_constraint(x, sh)
        if ck is not None:
            cache[ck] = x
        return x

    # -- accounting ------------------------------------------------------

    def summary(self) -> Dict:
        """The ``regrid_plan`` obs record body.

        Coalescing axis (same chains on both sides, so the delta is pure
        coalescing): ``constraints_before``/``hops_before`` = every edge
        resolved and constrained independently; ``..._after`` = no-op
        edges elided and fan-out duplicates traced once.  Hop-selection
        axis: ``predicted_transfer_s``/``predicted_bytes`` price the
        cost-chosen chains, ``greedy_transfer_s``/``greedy_bytes`` the
        greedy ``regrid_steps`` decompositions of the same edges."""
        seen_shared: set = set()
        edges = noop = shared = 0
        c_before = c_after = h_before = h_after = 0
        s_after = b_after = 0.0
        s_greedy = b_greedy = 0.0
        for ep in self.edges.values():
            edges += 1
            c_before += ep.naive_constraints
            h_before += len(ep.shardings)
            s_greedy += ep.greedy_s
            b_greedy += ep.greedy_bytes
            if not ep.shardings:
                noop += 1
                continue
            if ep.share_key is not None and ep.share_key in seen_shared:
                shared += 1
                continue
            if ep.share_key is not None:
                seen_shared.add(ep.share_key)
            c_after += ep.constraints
            h_after += len(ep.shardings)
            s_after += ep.predicted_s
            b_after += ep.predicted_bytes
        return {
            "edges": edges, "noop_edges": noop, "shared_edges": shared,
            "constraints_before": c_before, "constraints_after": c_after,
            "hops_before": h_before, "hops_after": h_after,
            "predicted_transfer_s": s_after,
            "greedy_transfer_s": s_greedy,
            "predicted_bytes": b_after,
            "greedy_bytes": b_greedy,
        }


def build_regrid_plan(model, fusion: Dict, schedule) -> RegridPlan:
    """Walk ``schedule`` exactly as ``FFModel._apply`` will, mirroring its
    produced-layout bookkeeping, and plan every reshard edge once.  The
    result is deterministic for a (model, schedule, fusion) triple —
    ``_apply`` then consumes plans by (op name, input index)."""
    from jax.sharding import PartitionSpec as P

    from flexflow_tpu.parallel.placement import PlacementGroup
    from flexflow_tpu.strategy import ParallelConfig

    machine = model.machine
    plan = RegridPlan(machine)
    costs = _MeshCosts(machine)
    specs: Dict[int, Tuple] = {}
    dp = ParallelConfig.data_parallel(1, machine.num_devices)
    for t in model._inputs:
        specs[t.tid] = machine.global_entries(dp, ("n",), P("n"),
                                              rank=t.ndim)

    from flexflow_tpu.sim.cost_model import dtype_bytes

    def itemsize(t):
        return dtype_bytes(t.dtype)

    for entry in schedule:
        if isinstance(entry, PlacementGroup):
            for m in entry.members:
                if entry.device_rows is not None:
                    targets = [tuple(() for _ in range(t.ndim))
                               for t in m.inputs]
                else:
                    ins = m.input_specs()
                    if ins is None:
                        targets = [None] * len(m.inputs)
                    else:
                        targets = [machine.global_entries(
                            m.pc, m.AXIS_NAMES, spec, rank=t.ndim)
                            if spec is not None else None
                            for spec, t in zip(ins, m.inputs)]
                for i, (t, dst) in enumerate(zip(m.inputs, targets)):
                    src = specs.get(t.tid)
                    if dst is None or src is None:
                        continue  # group inputs skip unknown sources
                    plan.add_edge(m.name, i, src, dst, t.shape,
                                  itemsize(t), costs=costs, tid=t.tid)
                for t, spec in zip(m.all_outputs(), m.output_specs()):
                    if spec is not None:
                        specs[t.tid] = machine.global_entries(
                            m.pc, m.AXIS_NAMES, spec, rank=t.ndim)
            continue
        op = model.layers[entry]
        if entry in fusion:
            # fused LM head: the folded projection never runs and the
            # fused loss output records no layout (the legacy behavior)
            continue
        want = op.regrid_input_specs()
        if want is not None:
            for i, (t, spec) in enumerate(zip(op.inputs, want)):
                if spec is None:
                    continue
                dst = machine.global_entries(op.pc, op.AXIS_NAMES, spec,
                                             rank=t.ndim)
                src = specs.get(t.tid)
                if dst is None:
                    continue
                plan.add_edge(op.name, i, src, dst, t.shape, itemsize(t),
                              replicate_unknown=True, costs=costs,
                              tid=t.tid)
        for t, spec in zip(op.all_outputs(), op.output_specs()):
            if spec is not None:
                specs[t.tid] = machine.global_entries(
                    op.pc, op.AXIS_NAMES, spec, rank=t.ndim)
    return plan


# ---------------------------------------------------------------------------
# live-state migration accounting (elastic resize)


def plan_state_migration(old_model, new_model, params: Dict,
                         state: Optional[Dict] = None,
                         opt_state: Optional[Dict] = None) -> Dict:
    """Accounting plan for moving live train state between two MACHINES
    (the elastic runtime's 8->6 shrink, utils/elastic.py) — the
    cross-machine sibling of :class:`RegridPlan`.

    A resize cannot be expressed as in-mesh hops: no mesh spans the old
    and new device sets at once, so every leaf is gathered off its source
    layout (one hop, priced as the all-gather of its replicated form on
    the OLD machine's links) and re-placed sharded on the new layout (one
    hop, the sharded put's per-device slice traffic on the NEW machine —
    a leaf landing replicated pays the full broadcast instead).  Leaves
    whose source layout is already fully replicated skip the gather: a
    surviving device holds the whole value.

    Returns per-key rows plus the totals the ``elastic_resize`` obs
    record carries (``bytes``, ``hops``, ``predicted_s``).  Pure
    accounting — the actual movement is ``np.asarray`` + the new model's
    placement (``FFModel.place_state``), and this plan never touches
    device data."""
    import numpy as np

    from flexflow_tpu.sim.cost_model import dtype_bytes

    old_n = old_model.machine.num_devices
    new_n = new_model.machine.num_devices
    new_topo = new_model.machine.topology
    old_topo = old_model.machine.topology

    def shard_count(model, key):
        for op in model.layers:
            if op.param_key == key or op.name == key:
                return max(op.pc.num_parts, 1)
        return 1

    rows = []
    total_bytes = 0.0
    total_hops = 0
    total_s = 0.0
    trees = [("params", params)]
    if state:
        trees.append(("state", state))
    if opt_state:
        trees.append(("opt", opt_state))
    for tree_name, tree in trees:
        for key, sub in (tree or {}).items():
            kb = 0.0
            for leaf in (sub or {}).values():
                a = np.asarray(leaf) if not hasattr(leaf, "nbytes") else leaf
                kb += float(getattr(a, "size", 0)
                            * dtype_bytes(str(getattr(a, "dtype",
                                                      "float32"))))
            src_parts = shard_count(old_model, key)
            dst_parts = shard_count(new_model, key)
            hops = 0
            secs = 0.0
            if src_parts > 1:
                # gather the sharded source onto one surviving host copy:
                # half an all-reduce of the full value over the old links
                hops += 1
                secs += 0.5 * _allreduce(kb, tuple(range(old_n)), old_topo)
            if dst_parts > 1:
                # sharded re-place: each new device receives its slice
                hops += 1
                secs += kb / dst_parts / new_topo.ici_bandwidth \
                    + new_topo.ici_latency
            else:
                # replicated landing: full broadcast to every survivor
                hops += 1
                secs += 0.5 * _allreduce(kb, tuple(range(new_n)), new_topo)
            rows.append({"tree": tree_name, "key": key, "bytes": kb,
                         "src_parts": src_parts, "dst_parts": dst_parts,
                         "hops": hops, "predicted_s": secs})
            total_bytes += kb
            total_hops += hops
            total_s += secs
    return {"keys": len(rows), "bytes": total_bytes, "hops": total_hops,
            "predicted_s": total_s,
            "from_devices": old_n, "to_devices": new_n, "rows": rows}
