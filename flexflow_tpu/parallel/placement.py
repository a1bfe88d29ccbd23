"""Explicit operator placement: executing ops on the device subsets their
strategy names.

The reference pins each NMT op instance to a specific GPU via mapper tags
(nmt/rnn_mapper.cc:28-41, 131-135); ops pinned to disjoint GPU sets then
execute concurrently under Legion's async task graph — that is where its
operator parallelism and wavefront pipelining over chunk ops come from
(nmt/rnn.cu:298-326).  Under XLA a jitted program is ONE SPMD computation
over ONE device assignment, so subset placement cannot be a mapper decision
made outside the program; it has to be compiled INTO it.  The mechanism
here:

  * the machine is viewed as a mesh ``("_pg", *op_grid_axes)``: a leading
    *placement-group* axis of size ``num_devices / subset_size`` over the
    op's own partition grid;
  * ops placed on disjoint subsets (and mutually independent in the DAG)
    are merged into one PLACEMENT GROUP, executed by a single
    ``shard_map`` whose body switches on ``lax.axis_index("_pg")`` — each
    device-group runs exactly its own op's branch (MPMD expressed inside
    SPMD), device-groups owning no op contribute zeros that are never
    consumed;
  * each member's parameters are stacked along the group axis and sharded
    over it, so weights physically live only on the subset that computes
    with them;
  * the member's own grid (e.g. Linear's (c, n)) partitions work *within*
    its subset via the inner mesh axes, with shard_map's transpose
    inserting the cross-shard reductions (the reference's BWD2/updateGAS).

Supported placements: an aligned contiguous block ``[g*P, (g+1)*P)``
(P = the op's grid size); a constant-stride set ``{b + j*(N/P)}`` such as
``(0,2,4,6)`` (stride family, round 3); or — round 4, closing SURVEY
§2.4 — ANY other duplicate-free list (``(0,3,5,6)``, misaligned blocks,
conflicting whole-machine permutations), honored in its named order by
set-family per-device dispatch.  A single whole-machine *permutation* is
honored one level up: FFModel rebuilds its machine view on the permuted
order (model.py _permuted_machine_view).  Ops are groupable when they
declare their input partitioning (``Op.input_specs``) and either share
shapes/hyperparameters (``Op.placement_signature`` — the homogeneous
fast path, params stacked with their inner sharding kept) or join the
HETEROGENEOUS path: different op kinds as different branches of one
switch, params (and, round 4, state) flattened to padded f32 vectors
stacked over the group axis.  Round 4 generalizes hetero membership
beyond "same grid, agreeing outputs": the mesh is built on one OWNER
grid, members of any other grid shape (same subset size) join as
point-local guests with their specs rewritten through an axis
translation (a conv(2,2,1,.) hosts an LSTM(4,) guest), and members with
incompatible output avals occupy disjoint switch positions instead of
being refused.  That restores the reference's Legion-style concurrency
between *different* ops on disjoint device sets (embeds on one block
while LSTMs run on another, nmt/rnn.cu:298-326, nmt/rnn_mapper.cc:28-41).
Only duplicate device lists and ops without placed support degrade to
the replicated normalization in ``MachineModel.sharding`` with a warning.
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import Dict, List, Optional, Sequence, Tuple

from flexflow_tpu.ops.base import Op
from flexflow_tpu.ops.base import point_slice as _point_slice


@dataclasses.dataclass
class PlacementGroup:
    """A set of independent ops executing concurrently on disjoint device
    subsets: contiguous blocks, constant-stride sets (``strided``), or —
    the general "set" family (round 4) — arbitrary duplicate-free device
    lists honored in their NAMED order via ``device_rows``."""

    members: List[Op]
    indices: List[int]        # layer indices of members
    slots: List[int]          # device-block index per member
    subset_size: int          # devices per member (= pc.num_parts)
    n_groups: int             # machine blocks of that size
    strided: bool = False     # stride family: slot b owns {b + j*(N/P)}
    #: set family: row g of the placement mesh is exactly device_rows[g]
    #: (member order; the machine pads remaining devices as zero rows)
    device_rows: Optional[List[Tuple[int, ...]]] = None
    #: hetero owner grid: the mesh is built on these dims/axes; members
    #: with any other grid run as point-local guests with translated
    #: specs (round 4 — None means the first member's grid)
    owner_dims: Optional[Tuple[int, ...]] = None
    owner_axes: Optional[Tuple[str, ...]] = None
    #: placed-op overlap (round 10): per-member LEAF flags — a True
    #: member's params thread through the hetero runner as group-stacked
    #: leaf trees with their inner sharding preserved (the homogeneous
    #: stacking) instead of the block-replicated f32 ravel vector, which
    #: admits inner-sharded-param ops (e.g. channel-split linears) into
    #: one fused dispatch.  None means all-vector (legacy).
    leaf_members: Optional[List[bool]] = None


def placement_slot(op: Op, num_devices: int,
                   pc: Optional["ParallelConfig"] = None):
    """("block", g) when ``op``'s ParallelConfig names the contiguous
    device block ``[g*P, (g+1)*P)``; ("stride", b) when it names the
    constant-stride set ``{b + j*(N/P)}`` (VERDICT r2 #3b, e.g.
    ``devices=(0,2,4,6)``); ("set", devices) — round 4, closing
    SURVEY §2.4 — for ANY other duplicate-free list, honored in its
    NAMED order on a mesh whose rows are the listed devices (the
    reference's RnnMapper pins a task to any named GPU,
    nmt/rnn_mapper.cc:131-135).  None when the op cannot run placed
    (no placed support for this grid, duplicates, or a grid that does
    not divide the machine) — those normalize with a warning.

    ``pc`` overrides the op's own config — the simulator asks whether a
    CANDIDATE grid/device list would lower as a placement group (the
    dispatch-overhead gate, sim/collectives.py) without mutating the
    op."""
    if pc is None:
        pc = op.pc
    p = pc.num_parts
    if num_devices <= 1 or p > num_devices:
        return None
    if op.placement_signature() is None:
        return None
    if len(set(pc.devices)) != p or \
            any(d < 0 or d >= num_devices for d in pc.devices):
        return None  # duplicates / out-of-range ids: normalize + warn
    if p == num_devices and pc.devices == tuple(range(num_devices)):
        # canonical full-machine list: the normal (free) GSPMD path —
        # never a placement group
        return None
    if op.input_specs(pc) is None or \
            (op.init_state() and op.state_specs() is None):
        # block/stride execution impossible (no placed specs for this
        # grid, or stateful without placed-state support) — but
        # set-family point dispatch may still honor the list: an op
        # overriding point_forward slices its own windows from the FULL
        # replicated operands and needs neither (round 5, e.g. a
        # stride-2 spatial conv on ANY duplicate-free device list)
        return ("set", tuple(pc.devices)) if _set_eligible(op, pc) else None
    if num_devices % p:
        # block/stride tilings need P | N; set-family per-device dispatch
        # does not (its flat mesh just leaves more devices on the zero
        # branch), so e.g. a (1,3) grid on (0,3,5) of 8 is still honored
        return ("set", tuple(pc.devices)) if _set_eligible(op, pc) else None
    if p == num_devices:
        # non-canonical full-machine list (the canonical order returned
        # above): a single foreign permutation is absorbed by the
        # machine-view rebuild (model._permuted_machine_view) before ops
        # are built, so reaching here means CONFLICTING permutations —
        # honor each via per-device dispatch (resharding at entry/exit)
        return ("set", tuple(pc.devices)) if _set_eligible(op, pc) else None
    # block/stride detection is order-insensitive: a strict-subset grid is
    # placement-symmetric (which grid point lands on which member device
    # permutes shard routing only), so the device SET decides the family —
    # e.g. a permuted-machine remap listing a block in reversed order
    # stays a plain block
    devs = tuple(sorted(pc.devices))
    d0 = devs[0]
    g, rem = divmod(d0, p)
    if rem == 0 and devs == tuple(range(g * p, (g + 1) * p)):
        return ("block", g)
    s = num_devices // p
    if d0 < s and devs == tuple(d0 + j * s for j in range(p)):
        return ("stride", d0)
    return ("set", tuple(pc.devices)) if _set_eligible(op, pc) else None


def _set_eligible(op: Op, pc: Optional["ParallelConfig"] = None) -> bool:
    """Can ``op`` run under set-family per-device dispatch?  The runner
    computes each grid point from the FULL (replicated) operands via
    ``Op.point_forward``: the op must declare point capability
    (``point_placeable`` — by default the point-local bar; spatial
    conv/pool override it, their halos being static slices of the full
    input), and its OUTPUT specs must be single-axis entries dividing
    evenly (the assembler's vocabulary).  STATEFUL members (round 5)
    need placed-state specs AND a point_forward override that computes
    from the full input (BatchNorm: global statistics, zero
    collectives).  Ops on the default ``point_forward`` additionally
    need sliceable input and param specs (the default slices by spec;
    overriders slice their own windows)."""
    if pc is None:
        pc = op.pc
    if not op.point_placeable():
        return False
    if op.init_state() and (
            op.state_specs() is None
            or type(op).point_forward is Op.point_forward):
        return False
    sizes = dict(zip(op.AXIS_NAMES, pc.dims))

    def ok(spec, shape):
        # single-axis entries only, and every sharded dim must divide
        # evenly (the per-point slicer floor-divides; a ragged dim would
        # silently truncate)
        if spec is None:
            return False
        for d, e in enumerate(tuple(spec)):
            if e is None:
                continue
            if not isinstance(e, str):
                return False
            parts = sizes.get(e, 1)
            if parts > 1 and (d >= len(shape) or shape[d] % parts):
                return False
        return True

    outs = op.output_specs()
    if outs is None or not all(
            ok(s, t.shape) for s, t in zip(outs, op.all_outputs())):
        return False
    params = op.param_specs()
    if params:
        import jax

        shapes = jax.eval_shape(lambda: op.init_params(
            jax.random.PRNGKey(0)))
        if not all(ok(params[k], shapes[k].shape) for k in params):
            return False  # param point-slicing is shared by both paths
    if type(op).point_forward is Op.point_forward:
        if op.input_specs(pc) is None or not all(
                ok(s, t.shape)
                for s, t in zip(op.input_specs(pc), op.inputs)):
            return False
    return True


def _signature(op: Op) -> tuple:
    return (type(op).__name__, op.pc.dims,
            tuple((t.shape, t.dtype) for t in op.inputs),
            tuple((t.shape, t.dtype) for t in op.all_outputs()),
            op.placement_signature())


def _params_block_replicated(op: Op) -> bool:
    """True when ``op``'s params are replicated *within* its placement
    block under its grid (every spec axis has grid size 1) — the
    heterogeneous path carries params as one flat vector per block and
    cannot preserve inner param sharding."""
    specs = op.param_specs()
    if not specs:
        return True
    sizes = dict(zip(op.AXIS_NAMES, op.pc.dims))
    for spec in specs.values():
        for entry in spec:
            if entry is None:
                continue
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                if sizes.get(a, 1) != 1:
                    return False
    return True


def _state_block_replicated(op: Op) -> bool:
    """True when ``op``'s state rides the hetero f32 group vector without
    losing sharding or precision: state_specs exist, every entry is
    replicated within the block, and leaves are f32-family."""
    specs = op.state_specs()
    if specs is None:
        return False
    sizes = dict(zip(op.AXIS_NAMES, op.pc.dims))
    for spec in specs.values():
        for entry in spec:
            if entry is None:
                continue
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                if sizes.get(a, 1) != 1:
                    return False
    return all(str(l.dtype) in ("float32", "bfloat16", "float16")
               for l in op.init_state().values())


def _hetero_eligible(op: Op) -> bool:
    """Can ``op`` join a heterogeneous (mixed-kind) placement group?
    Round 4 lifts the round-3 stateless restriction: stateful members
    (e.g. BatchNorm) thread their state through a second group-stacked
    f32 vector, provided it is block-replicated."""
    if op.init_state() and not _state_block_replicated(op):
        return False
    if not _params_block_replicated(op):
        return False
    if op.output_specs() is None or any(s is None
                                        for s in op.output_specs()):
        return False
    return all(t.dtype != "int32" for t in op.all_outputs())


def _overlap_eligible(op: Op) -> bool:
    """Can ``op`` join a heterogeneous group as a LEAF member (placed-op
    overlap, round 10)?  Leaf members' params are carried as
    group-stacked leaf trees with their inner sharding preserved — the
    homogeneous stacking — instead of the block-replicated f32 ravel
    vector, so ``_params_block_replicated`` no longer gates them.  The
    member must be stateless (state still rides the ravel vector), have
    full placed specs, and run NATIVE on the group's owner grid (its
    param specs name its own grid axes — enforced at grouping time)."""
    if op.init_state():
        return False
    if op.param_specs() is None or op.input_specs() is None:
        return False
    if op.output_specs() is None or any(s is None
                                        for s in op.output_specs()):
        return False
    return all(t.dtype != "int32" for t in op.all_outputs())


def _axis_translation(op: Op, owner_dims, owner_axes):
    """Map each of ``op``'s grid axes to owner mesh axes such that the
    two linearizations (dim 0 fastest) coincide: every nontrivial guest
    dim must equal a product of CONSECUTIVE nontrivial owner dims.
    Returns {guest axis: tuple of owner axes, slowest-first (the
    PartitionSpec multi-axis convention)} or None if not expressible.
    Identity grids translate to themselves."""
    o = [(a, d) for a, d in zip(owner_axes, owner_dims) if d > 1]
    i = 0
    mapping = {}
    for ga, gd in zip(op.AXIS_NAMES, op.pc.dims):
        if gd == 1:
            continue
        prod, take = 1, []
        while prod < gd and i < len(o):
            take.append(o[i][0])
            prod *= o[i][1]
            i += 1
        if prod != gd:
            return None
        mapping[ga] = tuple(reversed(take))
    return mapping if i == len(o) else None


def _translate_spec(spec, mapping):
    """Rewrite a single-axis-entry PartitionSpec onto owner mesh axes."""
    from jax.sharding import PartitionSpec as P

    entries = []
    for e in tuple(spec):
        if e is None:
            entries.append(None)
            continue
        if not isinstance(e, str):
            return None  # multi-axis guest entries unsupported
        t = mapping.get(e, ())
        entries.append(None if len(t) == 0 else
                       (t[0] if len(t) == 1 else t))
    return P(*entries)


def _member_view(op: Op, owner_dims, owner_axes):
    """(native, mapping, in_specs, out_specs) of ``op`` on the owner
    mesh, or None when the member cannot run there.  Native members
    (exact same grid dims AND axis names) keep their specs and may be
    grid-aware (their placed hooks see the live owner axes); any other
    grid joins as a point-local GUEST with its specs rewritten through
    the axis translation."""
    native = (op.pc.dims == tuple(owner_dims)
              and op.AXIS_NAMES == tuple(owner_axes))
    if native:
        return True, None, list(op.input_specs()), list(op.output_specs())
    if not op.placed_local() or op.init_state():
        return None
    mapping = _axis_translation(op, owner_dims, owner_axes)
    if mapping is None:
        return None
    ins = [_translate_spec(s, mapping) for s in op.input_specs()]
    outs = [_translate_spec(s, mapping) for s in op.output_specs()]
    if any(s is None for s in ins) or any(s is None for s in outs):
        return None
    return False, mapping, ins, outs


def _out_positions_on(op: Op, out_specs, owner_sizes):
    """Per output position (live spec entries, rank, sharded-dim extents,
    dtype) — computed against owner-mesh specs so members of different
    grids compare in one vocabulary.  Entries naming only size-1 owner
    axes normalize to None, so a native spec like P("n","h","w","c") on
    a batch-only grid matches a guest's translated P("n",None,None,None)."""
    def live(e):
        names = e if isinstance(e, tuple) else (e,)
        return any(owner_sizes.get(a, 1) > 1 for a in names)

    out = []
    for t, spec in zip(op.all_outputs(), out_specs):
        raw = tuple(spec) if spec is not None else None
        entries = None
        sharded = []
        if raw is not None:
            entries = tuple(e if (e is not None and live(e)) else None
                            for e in raw)
            for d, e in enumerate(entries):
                if e is not None:
                    sharded.append((d, t.shape[d]))
        out.append((entries, t.ndim, tuple(sharded), t.dtype))
    return tuple(out)


def _hetero_compatible(a, b) -> bool:
    """Output-position compatibility of two position records: shared
    positions must agree on spec, rank and sharded-dim extents (unsharded
    dims are zero-padded to the union; sharded dims cannot be)."""
    for pa, pb in zip(a, b):
        if pa[:3] != pb[:3]:
            return False
    return True


def plan_schedule(layers: Sequence[Op], num_devices: int,
                  exclude: frozenset = frozenset(),
                  overlap: bool = False):
    """Dataflow schedule for ``layers``: a list whose entries are either a
    layer index (execute that op normally) or a :class:`PlacementGroup`
    (execute its members jointly, placed).  ``exclude`` holds layer
    indices that must stay un-placed (e.g. ops claimed by the fused-LM-head
    plan).  Placed ops out of original order are legal because scheduling
    is by dependencies, like the reference's Legion task graph — grouping
    independent ops can never create a cycle (a path between group members
    would make one an ancestor of the other, which grouping forbids).

    ``overlap`` (round 10, ``FFConfig.placed_overlap``) additionally
    admits ops failing only ``_params_block_replicated`` into mixed
    groups as LEAF members (see :func:`_overlap_eligible`): independent
    same-level placed ops with inner-sharded params — e.g. two
    channel-split linears on disjoint blocks — fuse into ONE grouped
    dispatch instead of serializing as sequential shard_maps.  A group
    holding a leaf member has its owner grid PINNED (leaf param specs
    name the member's own grid axes, so owner switches would orphan
    them); False keeps the legacy grouping exactly."""
    n = len(layers)
    prod_idx: Dict[int, int] = {}
    for i, op in enumerate(layers):
        for t in op.all_outputs():
            prod_idx[t.tid] = i
    deps: List[List[int]] = []
    anc: List[set] = []
    for i, op in enumerate(layers):
        d = sorted({prod_idx[t.tid] for t in op.inputs
                    if t.tid in prod_idx})
        deps.append(d)
        a = set()
        for p in d:
            a |= anc[p]
            a.add(p)
        anc.append(a)

    # ---- grouping ----
    # Same-signature joins first (the homogeneous fast path keeps inner
    # param sharding); a leftover op may then join a *grid-compatible*
    # group heterogeneously — mixed op kinds as different switch branches
    # (Legion concurrency between different ops, nmt/rnn.cu:298-326).
    groups: List[dict] = []
    open_by_sig: Dict[tuple, List[dict]] = {}
    open_by_grid: Dict[tuple, List[dict]] = {}
    group_of: Dict[int, int] = {}

    def conflicts(fam, g, slots):
        """Can slot ``g`` not coexist with ``slots``?  Block/stride slots
        collide on equality; set-family slots are device tuples and
        collide on any overlap."""
        if fam == "set":
            gs = set(g)
            return any(gs & set(s) for s in slots)
        return g in slots

    def join(grp, i, g, elig, leaf=False):
        grp["indices"].append(i)
        grp["slots"].append(g)
        grp["leaf"].append(leaf)
        grp["hetero_ok"] = grp["hetero_ok"] and (elig or leaf)
        grp["pinned"] = grp["pinned"] or leaf
        group_of[i] = grp["id"]

    def group_fits(member_ids, owner_dims, owner_axes):
        """Every member of ``member_ids`` can run on the owner grid
        (native, or as a translated point-local guest).  Output-aval
        compatibility is NOT required: incompatible members occupy
        disjoint output positions of the switch (round 4 — a 4-D spatial
        conv and a 2-D batch linear share one group)."""
        return all(_member_view(layers[j], owner_dims, owner_axes)
                   is not None for j in member_ids)

    for i, op in enumerate(layers):
        if i in exclude:
            continue
        slot = placement_slot(op, num_devices)
        if slot is None:
            continue
        fam, g = slot
        sig = _signature(op)
        # set-family groups are homogeneous-only: their per-device switch
        # slices operands by ONE shared spec set
        elig = fam != "set" and _hetero_eligible(op)
        # placed-op overlap (round 10): a vector-ineligible op may still
        # join mixed groups as a LEAF member when the knob is on
        oelig = (overlap and fam != "set" and not elig
                 and _overlap_eligible(op))
        placed = False
        for grp in open_by_sig.get(sig, []):
            if grp["family"] != fam or conflicts(fam, g, grp["slots"]):
                continue
            if any(m in anc[i] for m in grp["indices"]):
                continue  # dependency path member -> op
            if grp["mixed"] and not group_fits(
                    grp["indices"] + [i],
                    grp["owner_dims"], grp["owner_axes"]):
                # hetero members arrived since and the candidate does not
                # fit the (possibly switched) owner grid
                continue
            if grp["mixed"] and oelig and (
                    tuple(grp["owner_dims"]) != op.pc.dims
                    or tuple(grp["owner_axes"]) != op.AXIS_NAMES):
                continue  # leaf members must run native on the owner
            join(grp, i, g, elig, oelig)
            placed = True
            break
        if not placed and (elig or oelig):
            for grp in open_by_grid.get((op.pc.num_parts, fam), []):
                if not grp["hetero_ok"] or conflicts(fam, g, grp["slots"]):
                    continue
                if any(m in anc[i] for m in grp["indices"]):
                    continue
                if oelig:
                    # leaf candidate: native on the current owner, or the
                    # owner repins to its grid (only while no other leaf
                    # member has pinned it)
                    native = (tuple(grp["owner_dims"]) == op.pc.dims
                              and tuple(grp["owner_axes"])
                              == op.AXIS_NAMES)
                    owner = (grp["owner_dims"], grp["owner_axes"])
                    if not native:
                        if grp["pinned"]:
                            continue
                        owner = (op.pc.dims, op.AXIS_NAMES)
                    if not group_fits(grp["indices"] + [i], *owner):
                        continue
                else:
                    # candidate on the group's current owner grid ...
                    owner = (grp["owner_dims"], grp["owner_axes"])
                    if not group_fits(grp["indices"] + [i], *owner):
                        # ... or the candidate's grid becomes the owner
                        # (it may refine the current one, e.g. a spatial
                        # conv joining batch-grid guests — round 4),
                        # unless a leaf member pinned it
                        if grp["pinned"]:
                            continue
                        owner = (op.pc.dims, op.AXIS_NAMES)
                        if not group_fits(grp["indices"] + [i], *owner):
                            continue
                grp["owner_dims"], grp["owner_axes"] = owner
                join(grp, i, g, elig, oelig)
                grp["mixed"] = True
                placed = True
                break
        if not placed:
            grp = {"id": len(groups), "indices": [i], "slots": [g],
                   "subset": op.pc.num_parts, "hetero_ok": elig or oelig,
                   "family": fam, "mixed": False, "leaf": [oelig],
                   "pinned": oelig,
                   "owner_dims": op.pc.dims, "owner_axes": op.AXIS_NAMES}
            groups.append(grp)
            open_by_sig.setdefault(sig, []).append(grp)
            if elig or oelig:
                open_by_grid.setdefault(
                    (op.pc.num_parts, fam), []).append(grp)
            group_of[i] = grp["id"]

    # ---- merge into schedule nodes + topological order ----
    # Merging keeps each group acyclic (a path between members would make
    # one an ancestor of the other), but cycles can still arise BETWEEN two
    # multi-member group nodes (A->B and C->D with {A,D} and {B,C} merged).
    # When the topological sort detects one, split the last-added member
    # out of an involved multi-member group and retry — each split strictly
    # shrinks a group, so this terminates.
    while True:
        node_members: List[List[int]] = []
        node_of_layer: Dict[int, int] = {}
        node_group: List[Optional[int]] = []
        for i in range(n):
            if i in node_of_layer:
                continue
            if i in group_of:
                members = groups[group_of[i]]["indices"]
                nid = len(node_members)
                node_members.append(members)
                node_group.append(group_of[i])
                for j in members:
                    node_of_layer[j] = nid
            else:
                nid = len(node_members)
                node_members.append([i])
                node_group.append(None)
                node_of_layer[i] = nid

        nn = len(node_members)
        ndeps: List[set] = [set() for _ in range(nn)]
        nsucc: List[set] = [set() for _ in range(nn)]
        for nid, members in enumerate(node_members):
            for i in members:
                for p in deps[i]:
                    pn = node_of_layer[p]
                    if pn != nid:
                        ndeps[nid].add(pn)
                        nsucc[pn].add(nid)
        indeg = [len(d) for d in ndeps]
        heap = [(min(node_members[nid]), nid) for nid in range(nn)
                if indeg[nid] == 0]
        heapq.heapify(heap)
        schedule = []
        done = [False] * nn
        while heap:
            _, nid = heapq.heappop(heap)
            done[nid] = True
            gid = node_group[nid]
            if gid is None:
                schedule.append(node_members[nid][0])
            else:
                grp = groups[gid]
                is_set = grp["family"] == "set"
                schedule.append(PlacementGroup(
                    members=[layers[i] for i in grp["indices"]],
                    indices=list(grp["indices"]),
                    # set family: members occupy mesh rows 0..m-1 in join
                    # order; the remaining rows hold the unlisted devices
                    slots=(list(range(len(grp["indices"]))) if is_set
                           else list(grp["slots"])),
                    subset_size=grp["subset"],
                    n_groups=num_devices // grp["subset"],
                    strided=grp["family"] == "stride",
                    device_rows=(list(grp["slots"]) if is_set else None),
                    owner_dims=grp["owner_dims"],
                    owner_axes=grp["owner_axes"],
                    leaf_members=list(grp["leaf"])))
            for s in nsucc[nid]:
                indeg[s] -= 1
                if indeg[s] == 0:
                    heapq.heappush(heap, (min(node_members[s]), s))
        if len(schedule) == nn:
            return schedule
        split = None
        for nid in range(nn):
            if not done[nid] and node_group[nid] is not None \
                    and len(node_members[nid]) > 1:
                split = node_group[nid]
                break
        assert split is not None, "cycle without a splittable group"
        last = groups[split]["indices"].pop()
        groups[split]["slots"].pop()
        was_leaf = groups[split]["leaf"].pop()
        groups[split]["pinned"] = any(groups[split]["leaf"])
        fam_last, slot_last = placement_slot(layers[last], num_devices)
        grp = {"id": len(groups), "indices": [last],
               "slots": [slot_last],
               "subset": layers[last].pc.num_parts,
               "hetero_ok": False, "family": fam_last,
               "mixed": False, "leaf": [was_leaf], "pinned": was_leaf,
               "owner_dims": layers[last].pc.dims,
               "owner_axes": layers[last].AXIS_NAMES}
        groups.append(grp)
        group_of[last] = grp["id"]


def run_group(machine, group: PlacementGroup,
              params_by_member: List[Dict],
              inputs_by_member: List[List], train: bool,
              states_by_member: Optional[List[Dict]] = None,
              prestacked: Optional[List[bool]] = None,
              state_prestacked: Optional[List[bool]] = None):
    """Execute a placement group jointly.  Returns
    ``(outs_by_member, new_states_by_member)``: per member, the tuple of
    its output arrays (each sliced from the group-stacked result, so it
    physically lives on that member's device block) and its new state
    dict ({} for stateless members).  ``state_prestacked`` members'
    state arrives AND returns in the stacked (G, ...) block-resident
    layout (round 5 — no state byte crosses the group axis)."""
    if states_by_member is None:
        states_by_member = [{} for _ in group.members]
    hetero = len({_signature(op) for op in group.members}) > 1
    if group.device_rows is not None:
        return _run_group_set(machine, group, params_by_member,
                              inputs_by_member, train,
                              prestacked or [False] * len(group.members),
                              states_by_member,
                              state_prestacked
                              or [False] * len(group.members))
    if hetero:
        return _run_group_hetero(
            machine, group, params_by_member, inputs_by_member, train,
            states_by_member,
            prestacked or [False] * len(group.members),
            state_prestacked or [False] * len(group.members))
    return _run_group_homogeneous(
        machine, group, params_by_member, inputs_by_member, train,
        states_by_member,
        prestacked or [False] * len(group.members),
        state_prestacked or [False] * len(group.members))


def grid_index(j: int, dims, axes) -> Dict[str, int]:
    """Grid-linear ``j`` (dim 0 fastest — the Rect order) -> per-axis
    index dict."""
    idx = {}
    for a, d in zip(axes, dims):
        idx[a] = j % d
        j //= d
    return idx


def set_group_assignment(group: PlacementGroup,
                         axis_names: Tuple[str, ...]):
    """{device: (member, grid-linear, per-axis index dict)} of a
    set-family group — the contract the per-device dispatch executes:
    member m's grid point j (dim 0 fastest) runs on
    ``device_rows[m][j]``, the reference's RnnMapper semantics
    (nmt/rnn_mapper.cc:131-135)."""
    out = {}
    dims = group.members[0].pc.dims
    for m, row in enumerate(group.device_rows):
        for j, dev in enumerate(row):
            out[dev] = (m, j, grid_index(j, dims, axis_names))
    return out




def _assemble(shards, spec, sizes, axis_names, dims):
    """Inverse of _point_slice over the whole grid: stitch the per-point
    shards (grid-linear order, dim 0 fastest) back into the global
    tensor.  A grid axis absent from the spec replicates the output —
    keep the first copy."""
    import jax.numpy as jnp

    entries = tuple(spec)
    dim_of = {e: d for d, e in enumerate(entries) if e is not None}
    lists = list(shards)
    for a, p in zip(axis_names, dims):
        if p == 1:
            continue
        d = dim_of.get(a)
        nxt = []
        for g in range(len(lists) // p):
            chunk = lists[g * p:(g + 1) * p]
            nxt.append(jnp.concatenate(chunk, axis=d)
                       if d is not None else chunk[0])
        lists = nxt
    assert len(lists) == 1
    return lists[0]


def _run_group_set(machine, group: PlacementGroup,
                   params_by_member: List[Dict],
                   inputs_by_member: List[List], train: bool,
                   prestacked: Optional[List[bool]] = None,
                   states_by_member: Optional[List[Dict]] = None,
                   state_prestacked: Optional[List[bool]] = None):
    """Arbitrary-device-list members (round 4, closing SURVEY §2.4): an
    irregular list like ``(0,3,5,6)`` cannot be a mesh reordering (XLA
    admits ONE device assignment per computation; block/stride placement
    meshes work only because they reshape the canonical order), so the
    group runs on the canonical flat ``(_dev,)`` mesh and every device
    switches on its own id to the (member, grid point) the strategy
    assigned it — the reference's tag-based per-task pinning
    (nmt/rnn_mapper.cc:28-41) compiled into one SPMD computation.

    The price, paid at group entry/exit rather than silently dropping
    the placement (the pre-round-4 normalization): operands are
    replicated to all devices (each branch computes its point via
    ``Op.point_forward`` from the full inputs — which is also what
    admits spatial/halo and irregular-window members, round 5), and
    outputs return through a per-device stacked array.  PARAMS no
    longer pay that price: block-resident members
    (model._derive_block_params, set family) arrive as per-device point
    rows ``(N, *point_shape)`` sharded over ``_dev`` — each device
    reads row [0] of its local block, so no parameter byte crosses the
    tier at entry, and gradients/optimizer state stay resident the same
    way (the reference keeps weights on their op's GPUs,
    nmt/rnn.cu:159-296)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from flexflow_tpu.parallel.ring_attention import unchecked_shard_map

    ops = group.members
    op0 = ops[0]
    axes = op0.AXIS_NAMES
    dims = op0.pc.dims
    sizes = dict(zip(axes, dims))
    mesh = machine.flat_mesh()
    N = machine.num_devices
    assign = set_group_assignment(group, axes)
    out_specs_per_op = op0.output_specs()
    pspecs = op0.param_specs()
    sspecs = op0.state_specs() or {}
    k_in = len(op0.inputs)
    prestacked = prestacked or [False] * len(ops)
    states_by_member = states_by_member or [{} for _ in ops]
    state_prestacked = state_prestacked or [False] * len(ops)
    have_state = bool(states_by_member and states_by_member[0])
    state_keys = sorted(states_by_member[0]) if have_state else []

    flat_inputs = [x for xs in inputs_by_member for x in xs]
    param_in_specs = tuple(
        jax.tree.map(lambda _, pre=pre: P("_dev") if pre else P(), p)
        for p, pre in zip(params_by_member, prestacked))
    state_in_specs = tuple(
        jax.tree.map(lambda _, pre=pre: P("_dev") if pre else P(), st)
        for st, pre in zip(states_by_member, state_prestacked))

    def body(*args):
        sp_by_member = args[:len(ops)]
        st_by_member = args[len(ops):2 * len(ops)]
        flat = args[2 * len(ops):]
        dev = lax.axis_index("_dev")
        xs_by_member = [list(flat[m * k_in:(m + 1) * k_in])
                        for m in range(len(ops))]

        def branch_for(m, idx):
            def br(_):
                sp = sp_by_member[m]
                if prestacked[m]:
                    # per-device point row: [0] of the local (1, ...)
                    # block — already this point's slice, zero traffic
                    lp = jax.tree.map(lambda l: l[0], sp)
                else:
                    lp = {k: _point_slice(v, pspecs[k], sizes, idx)
                          for k, v in sp.items()}
                st = st_by_member[m]
                if state_prestacked[m]:
                    ls = jax.tree.map(lambda l: l[0], st)
                else:
                    ls = {k: _point_slice(v, sspecs[k], sizes, idx)
                          for k, v in st.items()}
                with jax.named_scope(ops[m].name):
                    outs, new_st = ops[m].point_forward(
                        lp, ls, xs_by_member[m], idx, sizes, train)
                outs = outs + tuple(new_st[k] for k in state_keys)
                return tuple(jnp.expand_dims(o, 0) for o in outs)
            return br

        owned = {d: branch_for(m, idx) for d, (m, _, idx) in assign.items()}
        shapes = jax.eval_shape(next(iter(owned.values())), 0)

        def zero_branch(_):
            return tuple(jnp.zeros(s.shape, s.dtype) for s in shapes)

        branches = [owned.get(d, zero_branch) for d in range(N)]
        return lax.switch(dev, branches, 0)

    n_out = len(out_specs_per_op)
    res = unchecked_shard_map(
        body, mesh,
        param_in_specs + state_in_specs + (P(),) * len(flat_inputs),
        tuple(P("_dev") for _ in range(n_out + len(state_keys))))(
            *params_by_member, *states_by_member, *flat_inputs)
    new_states = []
    if state_keys:
        import numpy as _np

        for m, (row, spre) in enumerate(zip(group.device_rows,
                                            state_prestacked)):
            st = {}
            for i, k in enumerate(state_keys):
                r = res[n_out + i]
                if spre:
                    # keep the (N, ...) per-device storage with only
                    # this member's rows live — a static boolean mask,
                    # row-local (slicing would gather across devices)
                    mask = _np.zeros((N,) + (1,) * (r.ndim - 1), bool)
                    mask[list(row)] = True
                    st[k] = jnp.where(jnp.asarray(mask), r,
                                      jnp.zeros_like(r))
                else:
                    st[k] = _assemble([r[d] for d in row], sspecs[k],
                                      sizes, axes, dims)
            new_states.append(st)
    else:
        new_states = [{} for _ in ops]
    res = res[:n_out]

    out = []
    repl = machine.replicated()
    for m, row in enumerate(group.device_rows):
        vals = []
        for r, spec in zip(res, out_specs_per_op):
            shards = [r[d] for d in row]  # grid-linear order by contract
            v = _assemble(shards, spec, sizes, axes, dims)
            # explicit replicated waypoint: the row-gather out of the
            # per-device stacked layout has no efficient GSPMD lowering
            # to an arbitrary grid sharding — without the waypoint the
            # partitioner takes the same replicate-then-slice path
            # anyway, but as an "involuntary full rematerialization"
            # (warned); stating it keeps the program identical and the
            # compile log clean
            v = lax.with_sharding_constraint(v, repl)
            v = lax.with_sharding_constraint(
                v, machine.sharding(ops[m].pc, axes, spec))
            vals.append(v)
        out.append(tuple(vals))
    return out, new_states


def _run_group_homogeneous(machine, group: PlacementGroup,
                           params_by_member: List[Dict],
                           inputs_by_member: List[List], train: bool,
                           states_by_member: List[Dict],
                           prestacked: Optional[List[bool]] = None,
                           state_prestacked: Optional[List[bool]] = None):
    """Same-signature members: params (and state, round 3 — lifting the
    BatchNorm exclusion) stacked leaf-wise over the group axis with their
    inner sharding preserved; every branch shares one output aval.
    Branches run ``sharded_forward``, so grid-aware ops (spatial-halo
    convs, global-stats BatchNorm) see the live inner mesh axes."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from flexflow_tpu.parallel.ring_attention import unchecked_shard_map

    ops = group.members
    op0 = ops[0]
    G = group.n_groups
    axes = op0.AXIS_NAMES
    mesh = machine.placement_mesh(op0.pc.dims, axes,
                                  strided=group.strided)
    slots = group.slots
    k_in = len(op0.input_specs())

    prestacked = prestacked or [False] * len(ops)
    state_prestacked = state_prestacked or [False] * len(ops)

    def make_stacker(flags):
        """(G, ...) group-stacked leaf merger.  BLOCK-RESIDENT members
        (model._derive_block_params) arrive already stacked and
        _pg-sharded — their rows merge by a one-hot mask-sum, all
        block-local, so no byte crosses the group axis (on a two-tier
        machine, DCN); legacy unstacked members go through jnp.stack as
        before (GSPMD reshards them to the group layout).  Shared by
        params (``prestacked`` flags) and, round 5, state
        (``state_prestacked``)."""
        def stack(*member_leaves):
            by = {}
            pre = []
            for leaf, g, p in zip(member_leaves, slots, flags):
                if p:
                    io = jax.lax.broadcasted_iota(
                        jnp.int32, (G,) + (1,) * (leaf.ndim - 1), 0)
                    pre.append(jnp.where(io == g, leaf,
                                         jnp.zeros_like(leaf)))
                else:
                    by[g] = leaf
            out = None
            if by:
                z = jnp.zeros_like(next(iter(by.values())))
                out = jnp.stack([by.get(g, z) for g in range(G)])
            for v in pre:
                out = v if out is None else out + v
            return out
        return stack

    # ---- stack params along the group axis (zeros in unowned blocks) ----
    have_params = bool(params_by_member and params_by_member[0])
    if have_params:
        stacked = jax.tree.map(make_stacker(prestacked),
                               *params_by_member)
        pspecs = {k: P("_pg", *spec)
                  for k, spec in op0.param_specs().items()}
    else:
        stacked = {}
        pspecs = {}
    # ---- state threaded the same way (state_specs gates placement) ----
    have_state = bool(states_by_member and states_by_member[0])
    if have_state:
        stacked_state = jax.tree.map(make_stacker(state_prestacked),
                                     *states_by_member)
        sspecs = {k: P("_pg", *spec)
                  for k, spec in op0.state_specs().items()}
        state_keys = sorted(states_by_member[0])
    else:
        stacked_state = {}
        sspecs = {}
        state_keys = []

    in_specs = (pspecs, sspecs) + tuple(op0.input_specs()) * len(ops)
    n_out = len(op0.output_specs())
    out_specs = tuple(P("_pg", *spec) for spec in op0.output_specs()) + \
        tuple(P("_pg", *op0.state_specs()[k]) for k in state_keys)
    flat_inputs = [x for xs in inputs_by_member for x in xs]

    def body(sp, st, *flat):
        local_params = jax.tree.map(lambda a: a[0], sp)
        local_state = jax.tree.map(lambda a: a[0], st)
        gidx = lax.axis_index("_pg")
        xs_by_member = [list(flat[m * k_in:(m + 1) * k_in])
                        for m in range(len(ops))]

        # collective preludes (halo exchange, cross-shard statistics) run
        # for every member UNCONDITIONALLY — member inputs are replicated
        # over the group axis, so this is uniform across device blocks;
        # collectives inside the switch branches would be illegal SPMD
        aux_by_member = []
        for m in range(len(ops)):
            with jax.named_scope(ops[m].name):
                aux_by_member.append(
                    ops[m].placed_prelude(xs_by_member[m], train))

        def branch_for(m):
            def br(_):
                with jax.named_scope(ops[m].name):
                    res, new_st = ops[m].sharded_forward(
                        local_params, local_state, xs_by_member[m], train,
                        aux=aux_by_member[m])
                outs = res if isinstance(res, tuple) else (res,)
                outs = outs + tuple(new_st[k] for k in state_keys)
                return tuple(jnp.expand_dims(o, 0) for o in outs)
            return br

        owned = {g: branch_for(m) for m, g in enumerate(slots)}
        shapes = jax.eval_shape(owned[slots[0]], 0)

        def zero_branch(_):
            return tuple(jnp.zeros(s.shape, s.dtype) for s in shapes)

        branches = [owned.get(g, zero_branch) for g in range(G)]
        return lax.switch(gidx, branches, 0)

    res = unchecked_shard_map(body, mesh, in_specs, out_specs)(
        stacked, stacked_state, *flat_inputs)
    new_states = []
    for j, g in enumerate(slots):
        if state_prestacked[j]:
            # block-resident member: return the FULL stacked (G, ...)
            # array with only this member's row live — a one-hot mask is
            # row-local, whereas slicing row g would gather across _pg
            import jax as _jax

            st = {}
            for i, k in enumerate(state_keys):
                r = res[n_out + i]
                io = _jax.lax.broadcasted_iota(
                    jnp.int32, (G,) + (1,) * (r.ndim - 1), 0)
                st[k] = jnp.where(io == g, r, jnp.zeros_like(r))
            new_states.append(st)
        else:
            new_states.append({k: res[n_out + i][g]
                               for i, k in enumerate(state_keys)})
    res = res[:n_out]
    # Constrain each sliced member output to its pc's normalized sharding
    # (grid over the fast global axes, replicated over the rest).  This
    # splits the stacked->consumer regrid into an explicit gather over the
    # group axis plus a free slice; without the waypoint GSPMD relates the
    # stacked layout to the consumer's (e.g. full-DP) layout in one jump
    # and falls back to involuntary full rematerialization in the backward.
    out = []
    for g, m in zip(slots, ops):
        vals = []
        for r, spec in zip(res, op0.output_specs()):
            v = r[g]
            if spec is not None:
                v = lax.with_sharding_constraint(
                    v, machine.sharding(m.pc, m.AXIS_NAMES, spec))
            vals.append(v)
        out.append(tuple(vals))
    return out, new_states


def _run_group_hetero(machine, group: PlacementGroup,
                      params_by_member: List[Dict],
                      inputs_by_member: List[List], train: bool,
                      states_by_member: Optional[List[Dict]] = None,
                      prestacked: Optional[List[bool]] = None,
                      state_prestacked: Optional[List[bool]] = None):
    """Mixed-kind members (round 3; generalized round 4): each member is
    its own switch branch.

    lax.switch requires every branch to return identical avals, and the
    members' param trees don't mirror, so:

      * params: each member's tree is flattened, raveled to ONE f32
        vector, zero-padded to the group max and stacked over the group
        axis — sharded ``P("_pg")``, so weights still physically live only
        on the block that computes with them (the branch unflattens its
        slice back to shapes/dtypes).  Grouping admits only members whose
        params are replicated within their block
        (:func:`_params_block_replicated`), so no inner sharding is lost.
      * state (round 4, lifting the stateless restriction): threaded the
        same way through a SECOND group-stacked f32 vector; the branch
        unflattens, runs, and re-ravels its new state, which returns as
        an extra output position (``_state_block_replicated`` gates
        eligibility, so no inner sharding is lost here either).
      * grids (round 4): the mesh is built on the group's OWNER grid
        (``group.owner_dims/axes``); members with the exact owner grid
        are native and may be grid-aware (their placed hooks see the
        live axes — e.g. a spatial conv's halo ppermutes), while any
        other grid of the same subset size joins as a point-local GUEST
        whose specs are rewritten through :func:`_axis_translation`
        (its single batch axis becomes a tuple of owner axes) — a
        conv(2,2,1,.) and an LSTM(4,) now share one switch.
      * inputs: per-member translated ``input_specs`` (counts and ranks
        may differ) — the flat argument list concatenates every member's
        inputs.
      * outputs: padded to the per-position union aval (grouping
        guaranteed shared positions agree on spec/rank/sharded extents —
        only unsharded dims pad); missing positions are zeros.  The
        caller crops each member's outputs back to its true
        shapes/dtypes.

    This is the reference's operator parallelism: different Legion tasks
    on disjoint GPU sets executing concurrently (nmt/rnn.cu:298-326),
    compiled into one SPMD computation.
    """
    import math as _math

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from flexflow_tpu.parallel.ring_attention import unchecked_shard_map

    ops = group.members
    op0 = ops[0]
    G = group.n_groups
    owner_dims = group.owner_dims or op0.pc.dims
    owner_axes = group.owner_axes or op0.AXIS_NAMES
    mesh = machine.placement_mesh(owner_dims, owner_axes,
                                  strided=group.strided)
    slots = group.slots
    if states_by_member is None:
        states_by_member = [{} for _ in ops]
    views = [_member_view(m, owner_dims, owner_axes) for m in ops]
    assert all(v is not None for v in views), \
        "grouping admitted a member the owner grid cannot host"

    def check_f32_family(leaves, what, name):
        # the vector rides through f32: exact for f32/bf16/f16 leaves,
        # lossy for anything else — fail loudly rather than corrupt
        for l in leaves:
            if str(l.dtype) not in ("float32", "bfloat16", "float16"):
                raise TypeError(
                    f"heterogeneous placement of {name!r}: {what} dtype "
                    f"{l.dtype} does not round-trip through the f32 "
                    f"group vector")

    def ravel_tree(tree, what, name):
        leaves, treedef = jax.tree.flatten(tree)
        check_f32_family(leaves, what, name)
        vec = jnp.concatenate([l.ravel().astype(jnp.float32)
                               for l in leaves]) \
            if leaves else jnp.zeros((0,), jnp.float32)
        return vec, (treedef, [(l.shape, str(l.dtype)) for l in leaves])

    # ---- params and state: flatten -> f32 ravel -> pad -> stack ----
    # BLOCK-RESIDENT members (model._derive_block_params) arrive as
    # stacked (G, ...) leaves.  Their group vector is built ROW-WISE —
    # reshape (G, -1) keeping the sharded group dim, concat along the
    # vector dim, pad, one-hot-mask the member's row — every op per-row
    # local, so no parameter byte crosses the group axis (a row SLICE
    # would: GSPMD lowers cross-_pg slicing to gathers, measured as MORE
    # collectives than the legacy restack)
    prestacked = prestacked or [False] * len(ops)
    leaf_flags = list(group.leaf_members or [False] * len(ops))
    metas = []
    legacy = []        # (slot, 1-D vec) for plain members
    pre_rows = []      # (slot, (G, L_m) row-local vectors) for prestacked
    leaf_trees = []    # (G, ...)-stacked leaf trees for LEAF members
    leaf_specs = []    # matching P("_pg", *spec) pytrees
    leaf_pos = {}      # member index -> position in leaf_trees
    for mi, (m, p, g, pre) in enumerate(zip(ops, params_by_member, slots,
                                            prestacked)):
        if leaf_flags[mi]:
            # LEAF member (placed-op overlap, round 10): params keep
            # their leaf structure and inner sharding, group-stacked
            # exactly like the homogeneous path — zeros in unowned rows
            # for legacy arrival, a row-local one-hot mask for
            # block-resident (G, ...) arrival.  Leaf members run native
            # on the owner grid (grouping pinned it), so their param
            # specs name live mesh axes.
            pspecs = m.param_specs()
            tree = {}
            for k, l in p.items():
                if pre:
                    io = jax.lax.broadcasted_iota(
                        jnp.int32, (G,) + (1,) * (l.ndim - 1), 0)
                    tree[k] = jnp.where(io == g, l, jnp.zeros_like(l))
                else:
                    z = jnp.zeros_like(l)
                    tree[k] = jnp.stack([l if gg == g else z
                                         for gg in range(G)])
            leaf_pos[mi] = len(leaf_trees)
            leaf_trees.append(tree)
            leaf_specs.append({k: P("_pg", *pspecs[k]) for k in tree})
            metas.append(None)
        elif pre:
            leaves, treedef = jax.tree.flatten(p)
            check_f32_family(leaves, "param", m.name)
            for l in leaves:
                assert l.shape[0] == G, (
                    f"block-resident leaf of {m.name!r} stacked for "
                    f"{l.shape[0]} groups, mesh has {G} — mis-stacked "
                    f"storage would scramble rows silently")
            rowvec = jnp.concatenate(
                [l.reshape(G, -1).astype(jnp.float32) for l in leaves],
                axis=1) if leaves else jnp.zeros((G, 0), jnp.float32)
            pre_rows.append((g, rowvec))
            metas.append((treedef,
                          [(l.shape[1:], str(l.dtype)) for l in leaves]))
        else:
            v, meta = ravel_tree(p, "param", m.name)
            legacy.append((g, v))
            metas.append(meta)
    lmax = max([r.shape[1] for _, r in pre_rows] +
               [v.shape[0] for _, v in legacy] + [0])
    by_slot = {g: jnp.pad(v, (0, lmax - v.shape[0])) for g, v in legacy}
    zero = jnp.zeros((lmax,), jnp.float32)
    stacked = jnp.stack([by_slot.get(g, zero) for g in range(G)])
    for g, rowvec in pre_rows:
        padded = jnp.pad(rowvec, ((0, 0), (0, lmax - rowvec.shape[1])))
        io = jax.lax.broadcasted_iota(jnp.int32, (G, 1), 0)
        stacked = stacked + jnp.where(io == g, padded,
                                      jnp.zeros_like(padded))
    # state rides a second group-stacked f32 vector; round 5: BLOCK-
    # RESIDENT state (stacked (G, ...) leaves) builds its rows the same
    # row-wise way as params — reshape (G, -1), concat, one-hot mask —
    # so no state byte crosses the group axis either
    state_prestacked = state_prestacked or [False] * len(ops)
    smetas = []
    s_legacy = []      # (slot, 1-D vec)
    s_pre_rows = []    # (slot, (G, L_m) row-local vectors)
    for m, st, g, spre in zip(ops, states_by_member, slots,
                              state_prestacked):
        if spre:
            leaves, treedef = jax.tree.flatten(st)
            check_f32_family(leaves, "state", m.name)
            for l in leaves:
                assert l.shape[0] == G, (
                    f"block-resident state leaf of {m.name!r} stacked "
                    f"for {l.shape[0]} groups, mesh has {G}")
            rowvec = jnp.concatenate(
                [l.reshape(G, -1).astype(jnp.float32) for l in leaves],
                axis=1) if leaves else jnp.zeros((G, 0), jnp.float32)
            s_pre_rows.append((g, rowvec))
            smetas.append((treedef,
                           [(l.shape[1:], str(l.dtype)) for l in leaves]))
        else:
            v, meta = ravel_tree(st, "state", m.name)
            s_legacy.append((g, v))
            smetas.append(meta)
    smax = max([r.shape[1] for _, r in s_pre_rows] +
               [v.shape[0] for _, v in s_legacy] + [0])
    s_by_slot = {g: jnp.pad(v, (0, smax - v.shape[0]))
                 for g, v in s_legacy}
    s_zero = jnp.zeros((smax,), jnp.float32)
    stacked_state = jnp.stack([s_by_slot.get(g, s_zero)
                               for g in range(G)])
    for g, rowvec in s_pre_rows:
        padded = jnp.pad(rowvec, ((0, 0), (0, smax - rowvec.shape[1])))
        io = jax.lax.broadcasted_iota(jnp.int32, (G, 1), 0)
        stacked_state = stacked_state + jnp.where(
            io == g, padded, jnp.zeros_like(padded))

    member_in_specs = [v[2] for v in views]
    in_specs = (P("_pg", None), P("_pg", None)) + tuple(leaf_specs) + \
        tuple(s for specs in member_in_specs for s in specs)
    flat_inputs = [x for xs in inputs_by_member for x in xs]
    # the members' REAL global output avals (declared Tensor dtypes can be
    # stale under compute-dtype propagation): crop/cast targets
    real_avals = []
    for m in range(len(ops)):
        def fwd(m=m):
            p = jax.tree.map(lambda l: l[slots[m]], params_by_member[m]) \
                if prestacked[m] else params_by_member[m]
            s = jax.tree.map(lambda l: l[slots[m]], states_by_member[m]) \
                if state_prestacked[m] else states_by_member[m]
            res, _ = ops[m].forward(p, s, inputs_by_member[m], train)
            return res if isinstance(res, tuple) else (res,)
        real_avals.append(jax.eval_shape(fwd))
    offs = [0]
    for specs in member_in_specs:
        offs.append(offs[-1] + len(specs))

    # Output positions: members CLUSTER by output-aval compatibility
    # (same translated specs / rank / sharded extents per position);
    # each cluster owns a disjoint contiguous range of switch positions,
    # so members with unrelated outputs — a 4-D spatial conv beside a
    # 2-D batch linear — still share one switch (round 4; previously a
    # grouping-time gate).  Within a cluster, unsharded dims pad to the
    # union aval as before.
    sizes = dict(zip(owner_axes, owner_dims))
    records = [_out_positions_on(m, v[3], sizes)
               for m, v in zip(ops, views)]
    clusters = []      # {"members": [i..], "record": union, "specs": []}
    cluster_of = []
    for i, rec in enumerate(records):
        for ci, cl in enumerate(clusters):
            if _hetero_compatible(cl["record"], rec):
                cl["members"].append(i)
                if len(rec) > len(cl["record"]):
                    cl["record"] = rec
                for k, spec in enumerate(views[i][3]):
                    if k >= len(cl["specs"]):
                        cl["specs"].append(spec)
                cluster_of.append(ci)
                break
        else:
            clusters.append({"members": [i], "record": rec,
                             "specs": list(views[i][3])})
            cluster_of.append(len(clusters) - 1)
    pos_off = [0]
    for cl in clusters:
        pos_off.append(pos_off[-1] + len(cl["record"]))
    n_pos = pos_off[-1]
    pos_spec = []
    for cl in clusters:
        pos_spec.extend(cl["specs"])
    assert len(pos_spec) == n_pos

    def unravel(vec, meta):
        treedef, leaf_meta = meta
        leaves, off = [], 0
        for shape, dtype in leaf_meta:
            size = int(_math.prod(shape))
            leaves.append(vec[off:off + size].reshape(shape).astype(dtype))
            off += size
        return jax.tree.unflatten(treedef, leaves)

    def body(sp, st, *rest):
        leaf_sp = rest[:len(leaf_trees)]
        flat = rest[len(leaf_trees):]
        local_vec = sp[0]
        local_svec = st[0]
        gidx = lax.axis_index("_pg")
        # collective preludes run for every member unconditionally (same
        # rationale as the homogeneous path: member inputs are replicated
        # over the group axis; collectives inside branches are illegal).
        # Guests are point-local by construction, so their preludes are
        # no-ops
        aux_by_member = []
        for m in range(len(ops)):
            with jax.named_scope(ops[m].name):
                aux_by_member.append(ops[m].placed_prelude(
                    list(flat[offs[m]:offs[m + 1]]), train))

        def raw_branch(m):
            def br(_):
                if leaf_flags[m]:
                    # local row of the group-stacked leaf tree (inner
                    # sharding intact) — no ravel round-trip
                    p = jax.tree.map(lambda a: a[0], leaf_sp[leaf_pos[m]])
                else:
                    p = unravel(local_vec, metas[m])
                s = unravel(local_svec, smetas[m])
                with jax.named_scope(ops[m].name):
                    res, new_st = ops[m].sharded_forward(
                        p, s, list(flat[offs[m]:offs[m + 1]]), train,
                        aux=aux_by_member[m])
                outs = res if isinstance(res, tuple) else (res,)
                nsv, _ = ravel_tree(new_st, "state", ops[m].name)
                nsv = jnp.pad(nsv, (0, smax - nsv.shape[0]))
                return outs, nsv
            return br

        shapes_by_m = [jax.eval_shape(lambda x, m=m: raw_branch(m)(x)[0],
                                      0) for m in range(len(ops))]
        # per-cluster union avals laid out over the global position range
        union = [None] * n_pos
        for ci, cl in enumerate(clusters):
            for k in range(len(cl["record"])):
                cands = [shapes_by_m[i][k] for i in cl["members"]
                         if len(shapes_by_m[i]) > k]
                shape = tuple(max(c.shape[d] for c in cands)
                              for d in range(cands[0].ndim))
                union[pos_off[ci] + k] = (
                    shape, jnp.result_type(*[c.dtype for c in cands]))

        def padded_branch(m):
            ci = cluster_of[m]

            def br(_):
                outs, nsv = raw_branch(m)(0)
                padded = []
                for k, (shape, dtype) in enumerate(union):
                    j = k - pos_off[ci]
                    if 0 <= j < len(outs):
                        o = outs[j].astype(dtype)
                        o = jnp.pad(o, [(0, shape[d] - o.shape[d])
                                        for d in range(o.ndim)])
                    else:
                        o = jnp.zeros(shape, dtype)
                    padded.append(jnp.expand_dims(o, 0))
                return tuple(padded) + (jnp.expand_dims(nsv, 0),)
            return br

        owned = {g: padded_branch(m) for m, g in enumerate(slots)}

        def zero_branch(_):
            return tuple(jnp.zeros((1,) + s, d) for s, d in union) + \
                (jnp.zeros((1, smax), jnp.float32),)

        return lax.switch(gidx, [owned.get(g, zero_branch)
                                 for g in range(G)], 0)

    out_specs = tuple(P("_pg", *spec) for spec in pos_spec) + \
        (P("_pg", None),)
    res = unchecked_shard_map(body, mesh, in_specs, out_specs)(
        stacked, stacked_state, *leaf_trees, *flat_inputs)
    new_svecs = res[n_pos]
    res = res[:n_pos]
    # crop each member's outputs back to its true global shapes/dtypes,
    # with the same anti-remat sharding waypoint as the homogeneous path
    out = []
    new_states = []
    for i, (g, m) in enumerate(zip(slots, ops)):
        base = pos_off[cluster_of[i]]
        vals = []
        for k, spec in enumerate(m.output_specs()):
            av = real_avals[i][k]
            v = res[base + k][g]
            if v.shape != av.shape:
                v = lax.slice(v, (0,) * av.ndim, av.shape)
            v = v.astype(av.dtype)
            if spec is not None:
                v = lax.with_sharding_constraint(
                    v, machine.sharding(m.pc, m.AXIS_NAMES, spec))
            vals.append(v)
        out.append(tuple(vals))
        if not states_by_member[i]:
            new_states.append({})
        elif state_prestacked[i]:
            # rebuild the stacked (G, ...) storage row-locally: reshape
            # the (G, smax) vector's columns, one-hot-mask the member's
            # row (slicing row g would gather across _pg)
            treedef, leaf_meta = smetas[i]
            leaves, off = [], 0
            for shape, dtype in leaf_meta:
                size = int(_math.prod(shape))
                seg = new_svecs[:, off:off + size] \
                    .reshape((G,) + tuple(shape)).astype(dtype)
                io = jax.lax.broadcasted_iota(
                    jnp.int32, (G,) + (1,) * len(shape), 0)
                leaves.append(jnp.where(io == g, seg,
                                        jnp.zeros_like(seg)))
                off += size
            new_states.append(jax.tree.unflatten(treedef, leaves))
        else:
            new_states.append(unravel(new_svecs[g], smetas[i]))
    return out, new_states
