"""Op / Tensor base abstractions.

Reference equivalents: ``Tensor`` (model.h:85-89) and ``Op``
(model.h:101-119).  Differences by design:

  * a Tensor here is *symbolic* (shape/dtype/producer); concrete values flow
    through the functional ``forward`` — there are no regions or partitions
    to materialize, XLA/GSPMD owns physical layout;
  * ``Op.forward`` is pure: ``(params, state, inputs) -> (output, state)``.
    backward() and update() have no per-op code — they are jax.grad plus the
    optimizer, with cross-replica reductions inserted by GSPMD (the role of
    the reference's per-op backward tasks and ``updateGAS``,
    cuda_helper.cu:57-71);
  * activations use NHWC (TPU/MXU-preferred), while the strategy grid keeps
    the reference's (w, h, c, n) dim order (conv_2d.cu:69-75) for
    strategy-file compatibility — the mapping lives in ``output_spec``.
"""

from __future__ import annotations

import itertools
import math
from typing import Dict, List, Optional, Sequence, Tuple

from flexflow_tpu.strategy import ParallelConfig

_tensor_ids = itertools.count()


class Tensor:
    """Symbolic tensor: static shape + dtype + producing op (model.h:85-89
    analog; ``adim`` -> shape, region/part -> sharding owned by the op)."""

    def __init__(self, shape: Tuple[int, ...], dtype: str = "float32",
                 producer: Optional["Op"] = None, name: str = ""):
        self.shape = tuple(int(s) for s in shape)
        self.dtype = dtype
        self.producer = producer
        self.name = name
        self.tid = next(_tensor_ids)

    @property
    def ndim(self) -> int:
        return len(self.shape)

    def size(self) -> int:
        return math.prod(self.shape)

    def __repr__(self):
        p = self.producer.name if self.producer else "input"
        return f"Tensor(name={self.name!r}, shape={self.shape}, from={p})"


def point_slice(arr, spec, sizes, idx):
    """Static slice of one grid point's block of ``arr`` per its
    PartitionSpec (single-axis-or-None entries — the set-family
    eligibility bar, parallel/placement.py _set_eligible).  ``sizes``
    maps axis name -> parts, ``idx`` maps axis name -> this point's
    index."""
    entries = tuple(spec) + (None,) * (arr.ndim - len(tuple(spec)))
    sl = []
    for d, e in enumerate(entries):
        parts = sizes.get(e, 1) if e is not None else 1
        if parts == 1:
            sl.append(slice(None))
        else:
            n = arr.shape[d] // parts
            sl.append(slice(idx[e] * n, (idx[e] + 1) * n))
    return arr[tuple(sl)]


def exchange_halo(x, axis_name: str, parts: int, k: int, dim: int):
    """Borrow the (k-1)/2 edge rows of each neighbor along mesh axis
    ``axis_name`` via ppermute and concatenate them onto tensor dim
    ``dim``.  Boundary shards receive ppermute's zeros — the zero padding
    of SAME-padded convs/pools.  Shared by every placed-grid op that
    needs halos (Conv2D, Pool2D), so boundary semantics can never
    diverge.  Must run OUTSIDE placement-group branch switches (see
    Op.placed_prelude)."""
    import jax.numpy as jnp
    from jax import lax

    r = (k - 1) // 2
    if r == 0 or parts == 1:
        return x
    fwd = [(i, i + 1) for i in range(parts - 1)]
    bwd = [(i + 1, i) for i in range(parts - 1)]
    lo = lax.ppermute(
        lax.slice_in_dim(x, x.shape[dim] - r, x.shape[dim], axis=dim),
        axis_name, fwd)
    hi = lax.ppermute(lax.slice_in_dim(x, 0, r, axis=dim),
                      axis_name, bwd)
    return jnp.concatenate([lo, x, hi], axis=dim)


class Op:
    """Base operator: named, with inputs, one output, a ParallelConfig, and
    a pure functional forward.  (model.h:101-119 analog.)"""

    #: mesh axis names for this op's grid, innermost (grid dim 0) first;
    #: subclasses override, e.g. ("w", "h", "c", "n") for 4-D CNN ops.
    AXIS_NAMES: Tuple[str, ...] = ("n",)

    #: a pure reshape of its input, which may compile to a bitcast that
    #: carries no metadata: the one kind of operator a compiled step need
    #: not name (FFModel.operator_table)
    IS_VIEW: bool = False

    def __init__(self, name: str, pc: ParallelConfig,
                 inputs: Sequence[Tensor]):
        if len(pc.dims) != len(self.AXIS_NAMES):
            raise ValueError(
                f"op {name!r}: ParallelConfig rank {pc.ndims} does not match "
                f"op grid rank {len(self.AXIS_NAMES)} ({self.AXIS_NAMES})"
            )
        self.name = name
        self.pc = pc
        self.inputs: List[Tensor] = list(inputs)
        self.output: Tensor = None  # set by subclass
        #: extra outputs (e.g. LSTM hy/cy); forward then returns a tuple
        self.outputs: List[Tensor] = None
        #: params-dict key; ops sharing a key share weights (the reference's
        #: SharedVariable across chunk ops, nmt/rnn.h:37-51) — the first op
        #: with a key initializes, gradients sum automatically in jax.grad
        self.param_key: str = name

    # ---- parameters ----------------------------------------------------

    def init_params(self, rng) -> Dict:
        """Init trainable params (reference: per-op INIT_PARA tasks, e.g.
        conv_2d.cu:374-419). {} for parameterless ops."""
        return {}

    def init_state(self) -> Dict:
        """Non-trainable state (e.g. batch-norm running stats)."""
        return {}

    # ---- compute -------------------------------------------------------

    def forward(self, params: Dict, state: Dict, xs: List, train: bool):
        """Pure forward. Returns (output, new_state)."""
        raise NotImplementedError

    # ---- sharding ------------------------------------------------------

    def output_spec(self):
        """PartitionSpec of the output over AXIS_NAMES."""
        raise NotImplementedError

    def output_specs(self) -> List:
        """One spec per output (multi-output ops override)."""
        return [self.output_spec()]

    def all_outputs(self) -> List[Tensor]:
        """Every output tensor (the single ``output`` unless the op sets
        ``outputs``)."""
        return self.outputs if self.outputs else [self.output]

    def param_specs(self) -> Dict:
        """PartitionSpec per param leaf (same tree structure as
        init_params)."""
        return {}

    # ---- explicit placement hooks (parallel/placement.py) --------------

    def input_specs(self, pc: "ParallelConfig" = None):
        """PartitionSpec per input over AXIS_NAMES, for executing this op
        under an explicit device-subset placement (shard_map group
        execution).  ``pc`` defaults to the op's own config; the strategy
        search passes candidates to ask whether a grid is placeable.
        None -> op does not support placed execution (under that grid)."""
        return None

    def placement_signature(self):
        """Hyperparameters determining this op's computation beyond its
        input/output shapes.  Two ops may share a placement group (execute
        concurrently on disjoint device subsets) only when their signatures
        match.  None -> op does not support placed execution."""
        return None

    def placed_prelude(self, xs: List, train: bool):
        """The COLLECTIVE part of placed execution, run OUTSIDE the
        placement group's branch switch (collectives inside lax.switch
        branches are illegal SPMD — non-owning device blocks would never
        reach them; member inputs are replicated over the group axis, so
        the prelude is uniform across blocks and therefore legal).
        Returns an aux value handed to :meth:`sharded_forward`.  Default:
        nothing to exchange."""
        return None

    def sharded_forward(self, params, state, xs: List, train: bool,
                        aux=None):
        """Forward as executed INSIDE a placement-group shard_map branch,
        where the op's grid axes (AXIS_NAMES with pc.dims > 1) are live
        mesh axes.  MUST be collective-free (see placed_prelude — Conv2D's
        halo exchange and BatchNorm's cross-shard statistics live there).
        Default: the plain forward."""
        return self.forward(params, state, xs, train)

    def placed_local(self) -> bool:
        """True when this op's placed execution under ITS grid is point-
        local (no collective prelude; sharded_forward == forward) — the
        eligibility bar for set-family per-device dispatch
        (parallel/placement.py).  Ops that don't override the placed
        hooks are local by construction; overriders refine per grid
        (e.g. conv/pool: spatial parts == 1)."""
        cls = type(self)
        return (cls.placed_prelude is Op.placed_prelude
                and cls.sharded_forward is Op.sharded_forward)

    def point_placeable(self) -> bool:
        """Can this op execute as per-device grid POINTS in a set-family
        placement group (parallel/placement.py _run_group_set)?  The
        runner replicates operands, so a point computes from the FULL
        inputs — an op overriding :meth:`point_forward` may slice
        arbitrary windows (halos WITHOUT collectives, round 5: the full
        input is available on every device, so the neighbor exchange
        that gates block/stride spatial placement is just a static
        slice here).  Default: the point-local bar (the round-4
        behavior)."""
        return self.placed_local()

    def point_forward(self, params, state, xs, idx, sizes, train):
        """One grid point's computation from FULL (replicated) operands:
        slice + compute, returning ``(tuple of this point's output
        blocks, new state dict)``.  ``params`` (and ``state``) arrive
        already point-sliced; ``idx``/``sizes`` map axis name -> point
        index / parts.  Default: point-slice the inputs by input_specs
        and run the plain forward — correct for point-local ops; ops
        with neighborhood dependencies (spatial conv/pool) override to
        slice halo windows, stateful ops (BatchNorm) to compute global
        statistics from the full input."""
        xs_pt = [point_slice(x, s, sizes, idx)
                 for x, s in zip(xs, self.input_specs())]
        res, new_state = self.forward(params, state, xs_pt, train)
        return (res if isinstance(res, tuple) else (res,)), new_state

    def state_specs(self):
        """PartitionSpec per state leaf for PLACED execution (state
        stacked over the placement-group axis like params).  None -> a
        stateful op cannot execute placed (the round-2 exclusion);
        stateless ops return {}."""
        return None if self.init_state() else {}

    def regrid_input_specs(self):
        """PartitionSpec per input (over AXIS_NAMES, under ``self.pc``)
        that this op's compute wants its inputs in — used by FFModel.apply
        to decompose producer->consumer grid changes into single-axis-move
        resharding steps GSPMD lowers without full rematerialization (the
        reference's implicit repartitioning, conv_2d.cu:171-208).  None ->
        no preference (GSPMD chooses); a None entry skips that input."""
        return None

    def output_sharding(self, machine):
        return machine.sharding(self.pc, self.AXIS_NAMES, self.output_spec())

    def validate_partitioning(self):
        """Grid dims must divide the tensor dims they partition — the
        equivalent of the reference's disjoint/complete partition asserts
        (conv_2d.cu:108-109).  Spatial (h, w) dims may split UNEVENLY
        (parts <= extent): XLA pads the short shard, mirroring the
        reference's restriction transform (conv_2d.cu:95-113) — this is
        what admits 2-way splits of Inception's 35/17 extents."""
        sizes = dict(zip(self.AXIS_NAMES, self.pc.dims))
        for t, spec in zip(self.all_outputs(), self.output_specs()):
            if spec is None:
                continue
            for d, entry in enumerate(spec):
                if entry is None:
                    continue
                axes = entry if isinstance(entry, tuple) else (entry,)
                parts = 1
                for a in axes:
                    parts *= sizes.get(a, 1)
                if t.shape[d] % parts == 0:
                    continue
                from flexflow_tpu.strategy import uneven_spatial_ok

                if all(a in ("h", "w") for a in axes) \
                        and uneven_spatial_ok(t.shape[d], parts):
                    continue  # uneven spatial split, padded by XLA
                raise ValueError(
                    f"op {self.name!r}: output dim {d} of size "
                    f"{t.shape[d]} not divisible by its partition "
                    f"count {parts} (grid {self.pc.dims})")

    def param_shardings(self, machine) -> Dict:
        """Shardings for placing params as jit inputs (canonical device
        assignment; see MachineModel.input_sharding)."""
        return {
            k: machine.input_sharding(self.pc, self.AXIS_NAMES, spec)
            for k, spec in self.param_specs().items()
        }

    def local_clone(self, pc: ParallelConfig):
        """A new op instance at *shard-local* shapes under ``pc`` — what one
        device computes.  Used by MeasuredCostModel to time real shard work
        (the reference measures each partition count the same way,
        scripts/cnn.h).  None -> analytic fallback."""
        return None

    # ---- cost model hooks (consumed by the simulator) ------------------

    def cost_signature(self) -> tuple:
        """Extra compute-determining hyperparameters that do NOT appear in
        input/output shapes (e.g. MoE expert count / hidden width).  Folded
        into MeasuredCostModel's cache key so ops with identical shapes but
        different internal work are never conflated."""
        return ()

    def flops_per_sample(self) -> float:
        """Forward FLOPs per sample (fwd+bwd modeled as 3x by the sim)."""
        return 0.0

    def shard_flops_fwd(self, pc: ParallelConfig):
        """Forward FLOPs of ONE shard under ``pc``, for ops whose work does
        not divide uniformly over the grid (terms sharded over different
        axes).  None -> flops_per_sample * batch / num_parts."""
        return None

    def param_bytes(self) -> int:
        return 0

    def __repr__(self):
        return (f"{type(self).__name__}(name={self.name!r}, grid={self.pc.dims}, "
                f"out={self.output.shape if self.output else None})")
