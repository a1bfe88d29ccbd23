"""FFModel: the layer DAG + training loop, equivalent of the reference's
FFModel (model.h:121-171, model.cc, model.cu) re-designed for XLA.

Reference behavior mapped here:

  * builder methods conv2d/pool2d/batch_norm/linear/concat/flat/softmax
    (model.h:126-153) build a named-op DAG; each op looks up its
    ParallelConfig in ``config.strategies`` and falls back to pure data
    parallelism (cnn.cc:76-86);
  * forward()/backward()/update() (model.cu:300-316) become ONE jitted
    ``train_step``: XLA sees the whole iteration — forward, jax.grad
    backward, SGD update — and schedules/fuses it globally, which is the
    TPU-native analog of Legion's asynchronous task graph for an iteration
    (SURVEY.md §3.1 "the hot loop");
  * per-op partitioning is applied as ``with_sharding_constraint`` on each
    op's output (and on its params at init) over the ONE global factored
    mesh, and repartitioning between differently-gridded
    producers/consumers — the role of Legion's implicit copies
    (conv_2d.cu:171-208) — is decomposed by ``_regrid_inputs`` into
    single-mesh-axis hops GSPMD lowers without full rematerialization;
  * ``update()``'s replica aggregation (updateGAS, cuda_helper.cu:57-71) is
    implicit: gradients of replicated params arrive all-reduced by GSPMD.

SGD semantics: ``v = mu*v + g + wd*p;  p -= lr*v`` with the loss averaged
over the *global* batch (see ops/softmax.py for why this normalization).
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional

from flexflow_tpu import obs
from flexflow_tpu.config import FFConfig
from flexflow_tpu.machine import MachineModel
from flexflow_tpu.obs.optrace import UPDATE_SCOPE
from flexflow_tpu.ops import (Concat, Conv2D, Flat, Linear, Op, Pool2D,
                              Softmax, Tensor)
from flexflow_tpu.ops.norm import BatchNorm
from flexflow_tpu.ops.pool import POOL_MAX
from flexflow_tpu.strategy import ParallelConfig, validate_strategy
from flexflow_tpu.utils.debug import print_tensor

# optimizer-state leaf-name suffix of the float32 master weights in
# mixed-precision (param_dtype != float32) training — the checkpoint
# format and place_state both key off it (utils/checkpoint.py strips the
# same literal when mapping a master back to its base leaf's sharding)
_MASTER_SUFFIX = "__master"


def _opt_leaf_base(k: str) -> str:
    """Base param leaf name of an optimizer-state leaf (identity for
    momentum buffers, strips the master suffix)."""
    return k[:-len(_MASTER_SUFFIX)] if k.endswith(_MASTER_SUFFIX) else k


def _cast_floats(tree, dtype):
    """``tree`` with its floating leaves cast to ``dtype`` (a step's
    compute dtype) and the others as they are."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda v: v.astype(dtype) if jnp.issubdtype(
        v.dtype, jnp.floating) else v, tree)


def _point_shape(shape, spec, sizes):
    """Shape of one grid point's slice of a ``shape``-d leaf under a
    single-axis PartitionSpec (the set-family residency layout)."""
    entries = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
    return tuple(s // (sizes.get(e, 1) if e is not None else 1)
                 for s, e in zip(shape, entries))


def _point_rows(tree, reg):
    """(N, *point_shape) per-device rows of ``tree``'s leaves per a
    set-family residency record — each named device's row holds the
    slice its grid point computes with (shared by init's param/state
    storage and _restack_state)."""
    import jax.numpy as jnp

    from flexflow_tpu.ops.base import point_slice
    from flexflow_tpu.parallel.placement import grid_index

    sizes = dict(zip(reg["axes"], reg["dims"]))
    out = {}
    for k, v in tree.items():
        # optimizer master leaves reuse their base param leaf's spec
        spec = reg["specs"][k] if k in reg["specs"] \
            else reg["specs"][_opt_leaf_base(k)]
        pshape = _point_shape(tuple(v.shape), spec, sizes)
        arr = jnp.zeros((reg["N"],) + pshape, v.dtype)
        for j, dev in enumerate(reg["row"]):
            arr = arr.at[dev].set(point_slice(
                v, spec, sizes,
                grid_index(j, reg["dims"], reg["axes"])))
        out[k] = arr
    return out


def _point_row_avals(tree, reg, shardings):
    """Abstract (ShapeDtypeStruct) counterpart of :func:`_point_rows`."""
    import jax

    sizes = dict(zip(reg["axes"], reg["dims"]))
    return {k: jax.ShapeDtypeStruct(
        (reg["N"],) + _point_shape(tuple(v.shape), reg["specs"][k],
                                   sizes),
        v.dtype, sharding=shardings[k]) for k, v in tree.items()}


def _registry_match(rec, m, entry, j, g) -> bool:
    """Does residency record ``rec`` describe member ``m`` at position
    ``j`` (slot ``g``) of placement group ``entry``?  Gates the
    prestacked fast path for params and state alike — a mismatched
    record (different schedule variant) falls back to member-view
    reassembly."""
    if not rec or rec["dims"] != m.pc.dims:
        return False
    if entry.device_rows is not None:
        return (rec.get("family") == "set"
                and rec["row"] == tuple(entry.device_rows[j]))
    return (rec.get("family", "block") == "block"
            and rec.get("slot") == g
            and rec["strided"] == entry.strided)


def _fully_partitioned(op) -> bool:
    """True when every param leaf of ``op`` is sharded over EVERY
    nontrivial axis of its grid — i.e. the per-point slices are disjoint
    (no replicated copies).  The eligibility bar for set-family
    block-resident storage (see _derive_block_params)."""
    sizes = dict(zip(op.AXIS_NAMES, op.pc.dims))
    for spec in op.param_specs().values():
        present = set()
        for e in tuple(spec):
            if e is None:
                continue
            present.update((e,) if isinstance(e, str) else e)
        for a, s in sizes.items():
            if s > 1 and a not in present:
                return False
    return True


class _TrainStep:
    """A jitted train step that remembers what it first ran on, and the
    host's side of every call.

    Every attribute (``lower``, ``trace``, ...) goes straight through to
    the ``jax.jit`` object.  Before its first call it keeps the
    arguments' shapes, dtypes and shardings as ``first_call`` and names
    itself its model's ``_ran_step``: ``FFModel.operator_table()`` lowers
    this same object for them, so the table it reads is of the program
    that ran and of no other.  That first call (tracing, lowering, the
    compilation or its fetch from the cache, the dispatch) runs under
    ``ff:entry.step_build``, whose late ``args`` hold the wall seconds
    each of JAX's stages took inside it; every later call under
    ``ff:runtime.step`` (``n``: the call's number), the host's dispatch
    of one step.  A call inside another trace is neither."""

    STAGES = ("trace", "lower", "backend", "cache_fetch")

    def __init__(self, model, jitted):
        import jax

        self._model = model
        self._jitted = jitted
        self._tracer = jax.core.Tracer
        self.first_call = None
        self.calls = 0

    def __call__(self, *args):
        if self.first_call is None:
            return self._build(args)
        if args and isinstance(args[-1], self._tracer):
            return self._jitted(*args)
        self.calls += 1
        with obs.span("ff:runtime.step", n=self.calls):
            return self._jitted(*args)

    def __getattr__(self, name):
        return getattr(self._jitted, name)

    def _build(self, args):
        self._remember(args)
        if self.first_call is None:
            return self._jitted(*args)
        model = self._model
        before = obs.summary()["counters"]
        with obs.span("ff:entry.step_build", ops=len(model.layers),
                      blocks=len(getattr(model, "recompute_blocks", ()))
                      ) as build:
            out = self._jitted(*args)
            after = obs.summary()["counters"]
            for stage in self.STAGES:
                wall = f"compile.{stage}_wall_s"
                build.args[stage + "_s"] = after.get(wall, 0.0) \
                    - before.get(wall, 0.0)
        return out

    def _remember(self, args):
        import jax

        leaves = jax.tree.leaves(args)
        if any(isinstance(a, jax.core.Tracer) for a in leaves):
            return            # called inside another trace: not a run
        self.first_call = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=getattr(a, "sharding", None))
            if hasattr(a, "shape") else a, args)
        self._model._ran_step = self


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None,
                 machine: Optional[MachineModel] = None):
        self.config = config or FFConfig()
        self.machine = machine or MachineModel()
        validate_strategy(self.config.strategies, self.machine.num_devices)
        self.machine = self._permuted_machine_view(self.machine)
        self.layers: List[Op] = []
        self._inputs: List[Tensor] = []
        self._train_step = None
        self._eval_step = None

    def _permuted_machine_view(self, machine: MachineModel) -> MachineModel:
        """Honor full-machine device PERMUTATIONS in the strategy (VERDICT
        r2 #3a; strategy.proto:9 allows any device map, and the reference's
        RnnMapper pins tasks to arbitrary GPUs, nmt/rnn_mapper.cc:131-135).

        XLA admits one device order per computation, so a permutation
        cannot coexist with the canonical order op-by-op — but it CAN be
        the machine view itself: when every non-canonical full-machine pc
        names the same permutation, rebuild the machine on that device
        order.  Those pcs become canonical on the new view (grid point k
        executes on exactly the device the strategy named); already-
        canonical full-machine pcs are relabeled harmlessly (a full-machine
        grid is placement-symmetric: shards are interchangeable and its
        collectives span the whole machine either way); strict-subset pcs
        are remapped through the inverse permutation onto the same
        *physical* devices and keep their honored-or-degraded treatment
        (placement_slot is order-insensitive, so a block that the remap
        lists in reversed order stays honored).  Conflicting permutations
        keep the status-quo normalization (one-shot warning).

        The rewritten strategy becomes THIS model's private config copy —
        the caller's FFConfig (and its strategies dict) is never mutated,
        so the same config can build further models or be serialized."""
        n = machine.num_devices
        canon = tuple(range(n))
        if n <= 1 or not self.config.strategies:
            return machine
        perms = {pc.devices for pc in self.config.strategies.values()
                 if tuple(sorted(pc.devices)) == canon
                 and pc.devices != canon}
        if len(perms) != 1:
            return machine
        perm = next(iter(perms))
        # visible signal (round-3 ADVICE): the scan runs before layers are
        # built, so a stale full-machine entry from a FOREIGN graph (a
        # shared or checkpoint-loaded strategy dict) can rebuild the view
        # on a permuted device order with unchanged semantics but changed
        # ordinal-based tier pricing — make that decision loggable.
        import logging

        logging.getLogger(__name__).info(
            "machine view rebuilt on the strategy file's whole-machine "
            "device permutation %s (entries naming ops outside this "
            "model also qualify — check the strategy dict if unexpected)",
            perm)
        inv = [0] * n
        for i, d in enumerate(perm):
            inv[d] = i
        from flexflow_tpu.strategy import Strategy

        remapped = Strategy()
        # sidecar blocks (pipeline schedule, simulator prediction) ride
        # along — they describe the plan, not any device-ordinal entry
        remapped.pipeline = self.config.strategies.pipeline
        remapped.predicted = self.config.strategies.predicted
        for name, pc in self.config.strategies.items():
            if tuple(sorted(pc.devices)) == canon:
                remapped[name] = ParallelConfig(pc.dims, canon)
            else:
                remapped[name] = ParallelConfig(
                    pc.dims, tuple(inv[d] for d in pc.devices))
        import copy

        self.config = copy.copy(self.config)
        self.config.strategies = remapped
        # topology is carried over by ordinal: tier pricing of a permuted
        # view is approximate (the simulator builds its own machines)
        return MachineModel([machine.devices[d] for d in perm],
                            machine.topology)

    # ------------------------------------------------------------------
    # graph building (model.h:126-153 API parity)

    def _pc(self, name: str, ndims: int) -> ParallelConfig:
        pc = self.config.strategies.get(name)
        if pc is None:
            pc = self.machine.default_pc(ndims)
        return pc

    def _add(self, op: Op) -> Tensor:
        for t in op.all_outputs():
            if any(s <= 0 for s in t.shape):
                raise ValueError(
                    f"op {op.name!r} produces an empty tensor {t.shape} — "
                    f"input too small for the layer stack (e.g. AlexNet "
                    f"needs 224x224 input)")
        op.validate_partitioning()
        self.layers.append(op)
        return op.output

    def create_input(self, shape, dtype: str = "float32",
                     name: str = "input") -> Tensor:
        t = Tensor(shape, dtype, None, name)
        self._inputs.append(t)
        return t

    def conv2d(self, name, input, out_channels, kernel_h, kernel_w,
               stride_h, stride_w, padding_h, padding_w,
               relu: bool = False) -> Tensor:
        return self._add(Conv2D(name, self._pc(name, 4), input, out_channels,
                                kernel_h, kernel_w, stride_h, stride_w,
                                padding_h, padding_w, relu))

    def pool2d(self, name, input, kernel_h, kernel_w, stride_h, stride_w,
               padding_h, padding_w, pool_type: str = POOL_MAX,
               relu: bool = True) -> Tensor:
        return self._add(Pool2D(name, self._pc(name, 4), input, kernel_h,
                                kernel_w, stride_h, stride_w, padding_h,
                                padding_w, pool_type, relu))

    def batch_norm(self, name, input, relu: bool = True) -> Tensor:
        return self._add(BatchNorm(name, self._pc(name, 4), input, relu))

    def linear(self, name, input, out_channels, relu: bool = True) -> Tensor:
        return self._add(Linear(name, self._pc(name, 2), input, out_channels,
                                relu))

    def concat(self, name, tensors: List[Tensor]) -> Tensor:
        return self._add(Concat(name, self._pc(name, 4), tensors))

    def add(self, name, x: Tensor, y: Tensor, relu: bool = False) -> Tensor:
        from flexflow_tpu.ops.elementwise import Add

        return self._add(Add(name, self._pc(name, 4), [x, y], relu))

    def flat(self, name, input) -> Tensor:
        return self._add(Flat(name, self._pc(name, 2), input))

    def softmax(self, name, input) -> Tensor:
        return self._add(Softmax(name, self._pc(name, 1), input))

    # ---- sequence-model builders (transformer/NMT op family) ----------

    def embed(self, name, input, vocab_size, embed_size,
              param_key: str = None, init_std: float = 0.05,
              multiplier: float = 1.0) -> Tensor:
        from flexflow_tpu.ops.embed import Embed

        return self._add(Embed(name, self._pc(name, 1), input, vocab_size,
                               embed_size, param_key,
                               compute_dtype=self.config.compute_dtype,
                               init_std=init_std, multiplier=multiplier))

    def tied_head(self, name, input, embed: Tensor,
                  logits_scaling: float = 1.0) -> Tensor:
        """The vocabulary projection through the matrix of the embedding
        that produced ``embed`` (one parameter under one key)."""
        from flexflow_tpu.ops.embed import TiedHead

        return self._add(TiedHead(name, self._pc(name, 2), input,
                                  embed.producer, logits_scaling))

    def pos_embed(self, name, input) -> Tensor:
        from flexflow_tpu.ops.seq_common import PosEmbed

        return self._add(PosEmbed(name, self._pc(name, 2), input))

    def layer_norm(self, name, input) -> Tensor:
        from flexflow_tpu.ops.seq_common import LayerNormSeq

        return self._add(LayerNormSeq(name, self._pc(name, 2), input))

    def add_seq(self, name, x: Tensor, y: Tensor,
                scale: float = 1.0) -> Tensor:
        from flexflow_tpu.ops.seq_common import AddSeq

        return self._add(AddSeq(name, self._pc(name, 2), [x, y], scale))

    def attention(self, name, input, num_heads,
                  causal: bool = False) -> Tensor:
        from flexflow_tpu.ops.attention import MultiHeadAttention

        return self._add(MultiHeadAttention(
            name, self._pc(name, 3), input, num_heads, causal,
            machine=self.machine))

    def moe(self, name, input, num_experts, d_ff, top_k: int = 2,
            capacity_factor: float = 2.0) -> Tensor:
        from flexflow_tpu.ops.moe import MixtureOfExperts

        return self._add(MixtureOfExperts(
            name, self._pc(name, 3), input, num_experts, d_ff, top_k,
            capacity_factor, machine=self.machine))

    def seq_linear(self, name, input, out_channels,
                   param_key: str = None, use_bias: bool = True) -> Tensor:
        from flexflow_tpu.ops.rnn_linear import RnnLinear

        return self._add(RnnLinear(name, self._pc(name, 2), input,
                                   out_channels, param_key, use_bias))

    def rms_norm(self, name, input, eps: float = 1e-5) -> Tensor:
        from flexflow_tpu.ops.seq_gated import RMSNormSeq

        return self._add(RMSNormSeq(name, self._pc(name, 2), input, eps))

    def gated_ffn(self, name, input, d_ff) -> Tensor:
        from flexflow_tpu.ops.seq_gated import GatedFFNSeq

        return self._add(GatedFFNSeq(name, self._pc(name, 2), input, d_ff))

    def latent_attention(self, name, input, num_heads, kv_rank, nope_dim,
                         rope_dim, v_dim, rope_theta, eps: float = 1e-5,
                         qk_norm: float = None,
                         head_gate: bool = False) -> Tensor:
        from flexflow_tpu.ops.latent_attention import LatentAttention

        return self._add(LatentAttention(
            name, self._pc(name, 3), input, num_heads, kv_rank, nope_dim,
            rope_dim, v_dim, rope_theta, eps, qk_norm, head_gate))

    def grouped_query_attention(self, name, input, num_heads, num_kv_heads,
                                head_dim, scale, rope=None, window=None,
                                gate: bool = False,
                                qk_norm: float = None) -> Tensor:
        from flexflow_tpu.ops.attention import GroupedQueryAttention

        return self._add(GroupedQueryAttention(
            name, self._pc(name, 3), input, num_heads, num_kv_heads,
            head_dim, scale, rope, window, gate, qk_norm))

    def gated_short_conv(self, name, input, taps) -> Tensor:
        """A gated short convolution (ops/short_conv.py): one product in,
        a depthwise causal convolution of ``taps`` taps between two
        gates, one product out."""
        from flexflow_tpu.ops.short_conv import GatedShortConv

        return self._add(GatedShortConv(name, self._pc(name, 2), input,
                                        taps))

    def ssm_mixer(self, name, input, num_heads, head_dim, d_state, d_conv,
                  chunk, conv_bias: bool = True,
                  eps: float = 1e-5) -> Tensor:
        """A Mamba-2 mixer as its three operators (ops/ssm.py):
        ``<name>_in``, ``<name>_scan`` and ``<name>_out``."""
        from flexflow_tpu.ops.ssm import SSMIn, SSMOut, SSMScan

        first = SSMIn(name + "_in", self._pc(name + "_in", 2), input,
                      num_heads, head_dim, d_state, d_conv, conv_bias)
        self._add(first)
        y = self._add(SSMScan(name + "_scan", self._pc(name + "_scan", 2),
                              first.xbc, first.delta, num_heads, head_dim,
                              d_state, chunk))
        return self._add(SSMOut(name + "_out", self._pc(name + "_out", 2),
                                y, first.z, input.shape[2], eps))

    def kda_mixer(self, name, input, num_heads, head_dim, taps,
                  lower_bound, eps: float = 1e-6) -> Tensor:
        """A Kimi-delta-attention mixer as its three operators
        (ops/kda.py): ``<name>_in``, ``<name>_scan`` and ``<name>_out``."""
        from flexflow_tpu.ops.kda import KDAIn, KDAOut, KDAScan

        first = KDAIn(name + "_in", self._pc(name + "_in", 2), input,
                      num_heads, head_dim, taps, lower_bound)
        self._add(first)
        o = self._add(KDAScan(name + "_scan", self._pc(name + "_scan", 2),
                              first.q, first.k, first.v, first.g, first.beta,
                              num_heads, head_dim, lower_bound))
        return self._add(KDAOut(name + "_out", self._pc(name + "_out", 2),
                                o, first.z, head_dim, input.shape[2], eps))

    def top_k_router(self, name, input, n_router, top_k, scale,
                     bias_update_rate: float = 1e-3,
                     score: str = "sigmoid",
                     denominator_eps: float = 0.0, n_group: int = 1,
                     topk_group: int = 1) -> Tensor:
        from flexflow_tpu.ops.expert_share import TopKRouter

        return self._add(TopKRouter(name, self._pc(name, 2), input,
                                    n_router, top_k, scale,
                                    bias_update_rate, score,
                                    denominator_eps, n_group, topk_group))

    def held_experts(self, name, input, gates, d_ff, experts_held, top_k,
                     capacity_factor: float = 2.0,
                     act: str = "silu") -> Tensor:
        from flexflow_tpu.ops.expert_share import HeldExperts

        return self._add(HeldExperts(name, self._pc(name, 2), input, gates,
                                     d_ff, experts_held, top_k,
                                     capacity_factor, act))

    def softmax_seq(self, name, logits: Tensor, labels: Tensor) -> Tensor:
        from flexflow_tpu.ops.softmax_dp import SoftmaxDP

        return self._add(SoftmaxDP(name, self._pc(name, 1), logits, labels))

    # ------------------------------------------------------------------
    # parameters

    def init(self, seed: Optional[int] = None, abstract: bool = False):
        """Initialize (params, state), placing each param with its op's
        sharding (reference: INIT_PARA tasks writing into replicated
        regions, conv_2d.cu:374-419).  With ``abstract=True`` the same
        traversal yields sharding-annotated ShapeDtypeStructs and nothing
        is materialized (used by the DISABLE_COMPUTATION-analog dry
        compile).  Under a caller's ``jax.jit`` the ``ff:entry.init``
        span times the Python tracing of the draws, which is what
        set-up pays."""
        import jax

        with obs.span("ff:entry.init", ops=len(self.layers),
                      abstract=int(abstract)) as sp:
            params, state = self._init(seed, abstract)
            sp.args["leaves"] = len(jax.tree.leaves((params, state)))
        return params, state

    def _init(self, seed: Optional[int], abstract: bool):
        import jax
        import jax.numpy as jnp

        seed = self.config.seed if seed is None else seed
        if self.machine.num_devices > 1:
            # mark honored placements BEFORE param placement asks for
            # shardings, so subset pcs the placement executor handles do
            # not draw a false "placement not honored" warning
            self._placement_schedule(frozenset())
        key = jax.random.PRNGKey(seed)
        all_ones = self.config.params_init == "ones"
        params: Dict[str, Dict] = {}
        state: Dict[str, Dict] = {}
        for op in self.layers:
            if op.param_key not in params:
                # shared weights: first op with the key initializes
                key, sub = jax.random.split(key)
                if abstract:
                    try:
                        p = jax.eval_shape(op.init_params, sub)
                    except (jax.errors.TracerArrayConversionError,
                            jax.errors.ConcretizationTypeError,
                            jax.errors.TracerBoolConversionError):
                        # init uses host-side (numpy) randomness —
                        # materialize on host; genuine bugs still propagate
                        p = op.init_params(sub)
                else:
                    p = op.init_params(sub)
                    if p and all_ones:
                        # PARAMETER_ALL_ONES parity (conv_2d.cu:393-398):
                        # deterministic all-ones weights, hand-checkable runs
                        p = {k: jnp.ones_like(v) for k, v in p.items()}
                # mixed precision: params are STORED in param_dtype; the
                # cast lands before placement so every storage family
                # (set rows / block stacks / plain) sizes off the cast
                p = self._cast_param_tree(p)
                bp = getattr(self, "_block_params", {}).get(op.param_key)
                if p and bp and bp.get("family") == "set":
                    # set-family residency (round 5): per-device POINT
                    # rows (N, *point_shape) on the flat mesh — device
                    # row[j] holds the slice grid point j computes with
                    sh = self._block_sharding(bp)
                    params[op.param_key] = _point_row_avals(p, bp, sh) \
                        if abstract else \
                        {k: jax.device_put(v, sh[k])
                         for k, v in _point_rows(p, bp).items()}
                elif p and bp:
                    # block-resident storage (see _derive_block_params):
                    # stacked (G, ...) with the op's row live, sharded
                    # over the placement mesh so each block holds only
                    # its own member's weights
                    G, slot = bp["G"], bp["slot"]
                    sh = self._block_sharding(bp)
                    if abstract:
                        params[op.param_key] = {
                            k: jax.ShapeDtypeStruct(
                                (G,) + tuple(v.shape), v.dtype,
                                sharding=sh[k])
                            for k, v in p.items()
                        }
                    else:
                        params[op.param_key] = {
                            k: jax.device_put(
                                jnp.zeros((G,) + tuple(v.shape),
                                          v.dtype).at[slot].set(v),
                                sh[k])
                            for k, v in p.items()
                        }
                elif p:
                    with self._honored_ctx():
                        shardings = op.param_shardings(self.machine)
                    if abstract:
                        params[op.param_key] = {
                            k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                                    sharding=shardings[k])
                            for k, v in p.items()
                        }
                    else:
                        params[op.param_key] = {
                            k: jax.device_put(v, shardings[k])
                            for k, v in p.items()
                        }
            s = op.init_state()  # state is per-op even under shared params
            if s:
                bs = getattr(self, "_block_state", {}).get(op.name)
                if bs and bs.get("family") == "set":
                    # per-device point rows, like set-family params
                    sh = self._block_sharding(bs)
                    state[op.name] = _point_row_avals(s, bs, sh) \
                        if abstract else \
                        {k: jax.device_put(v, sh[k])
                         for k, v in _point_rows(s, bs).items()}
                elif bs:
                    # block-resident state (round 5, VERDICT r4 #9):
                    # stacked (G, ...) with the op's row live, sharded
                    # over the placement mesh like its params
                    G, slot = bs["G"], bs["slot"]
                    sh = self._block_sharding(bs)
                    if abstract:
                        state[op.name] = {
                            k: jax.ShapeDtypeStruct(
                                (G,) + tuple(v.shape), v.dtype,
                                sharding=sh[k])
                            for k, v in s.items()}
                    else:
                        state[op.name] = {
                            k: jax.device_put(
                                jnp.zeros((G,) + tuple(v.shape),
                                          v.dtype).at[slot].set(v),
                                sh[k])
                            for k, v in s.items()}
                else:
                    # commit to a concrete (replicated) sharding so the first
                    # train step's input avals match later steps' outputs —
                    # uncommitted state would cost one extra full recompile
                    # (the abstract traversal names the same sharding: a
                    # caller that makes the state in one jitted call lays
                    # it out by these)
                    repl = self.machine.replicated()
                    state[op.name] = jax.tree.map(
                        (lambda v: jax.ShapeDtypeStruct(
                            v.shape, v.dtype, sharding=repl)) if abstract
                        else (lambda v: jax.device_put(v, repl)), s)
        return params, state

    # ------------------------------------------------------------------
    # mixed precision (perf round): param_dtype != float32 stores the
    # parameters low-precision (halved HBM/collective traffic) while a
    # float32 MASTER copy of every float leaf rides in the optimizer
    # state under ``<leaf>__master`` — update math runs in float32
    # against the masters and the stored params are re-cast from them on
    # write-back.  The opt tree stays exactly two levels deep
    # ({param_key: {leaf: array}}), which checkpointing and place_state
    # assume; master leaves map to their base leaf's sharding.

    def _mixed_precision(self) -> bool:
        return (getattr(self.config, "param_dtype", "float32")
                or "float32") != "float32"

    def _cast_param_tree(self, p):
        """Cast a freshly initialized param tree to the configured
        storage dtype — float leaves only; works on concrete arrays and
        the abstract (ShapeDtypeStruct) traversal alike."""
        import jax
        import jax.numpy as jnp

        if not p or not self._mixed_precision():
            return p
        dt = jnp.dtype(self.config.param_dtype)

        def cast(v):
            if not jnp.issubdtype(v.dtype, jnp.floating):
                return v
            if isinstance(v, jax.ShapeDtypeStruct):
                return jax.ShapeDtypeStruct(v.shape, dt,
                                            sharding=v.sharding)
            return v.astype(dt)

        return {k: cast(v) for k, v in p.items()}

    def master_opt_state(self, params):
        """The master-weight half of the optimizer state: a float32
        master per float param leaf (``<leaf>__master``), initialized as
        the upcast of the stored params (exact for a fresh bfloat16
        init — the cast that produced the stored copy is recovered
        losslessly only up to bf16 resolution, so init keeps the
        invariant params == masters.astype(param_dtype)).  None in plain
        float32 mode — the plain-SGD subclasses return this directly
        from init_opt_state."""
        import jax.numpy as jnp

        with obs.span("ff:entry.opt_state", ops=len(params)):
            if not self._mixed_precision():
                return None
            return {key: {k + _MASTER_SUFFIX: v.astype(jnp.float32)
                          for k, v in sub.items()
                          if jnp.issubdtype(v.dtype, jnp.floating)}
                    for key, sub in params.items()}

    def init_opt_state(self, params):
        with obs.span("ff:entry.opt_state", ops=len(params)):
            return self._init_opt_state(params)

    def _init_opt_state(self, params):
        import jax

        if not self._mixed_precision():
            return jax.tree.map(lambda p: p * 0.0, params)
        import jax.numpy as jnp

        out = {}
        for key, sub in params.items():
            d = {}
            for k, v in sub.items():
                if jnp.issubdtype(v.dtype, jnp.floating):
                    m = v.astype(jnp.float32)
                    d[k] = m * 0.0          # float32 momentum buffer
                    d[k + _MASTER_SUFFIX] = m
                else:
                    d[k] = v * 0
            out[key] = d
        return out

    def _opt_shardings(self, opt_state, psh):
        """{param_key: {opt leaf: sharding}} mirroring ``opt_state`` —
        master leaves share their base param leaf's sharding (same
        shape; shardings are dtype-agnostic)."""
        return {key: {k: psh[key][_opt_leaf_base(k)] for k in sub}
                for key, sub in opt_state.items()}

    def _param_shardings(self, params):
        """{param_key: {name: sharding}} mirroring ``params`` — the same
        shardings init() placed them with."""
        shardings = {}
        block = getattr(self, "_block_params", {})
        with self._honored_ctx():
            for op in self.layers:
                if op.param_key in params and op.param_key not in shardings:
                    bp = block.get(op.param_key)
                    if bp:
                        sh = self._block_sharding(bp)
                        shardings[op.param_key] = {
                            k: sh[k] for k in params[op.param_key]
                        }
                        continue
                    sh = op.param_shardings(self.machine)
                    shardings[op.param_key] = {
                        k: sh[k] for k in params[op.param_key]
                    }
        return shardings

    def _constrain_params(self, new_params, shardings):
        """Pin updated params to their init-time shardings inside the
        jitted step.  Without this the step's outputs carry whatever
        (default) shardings XLA picked, which differ from the explicitly
        placed inputs — so the SECOND call retraces and recompiles the
        whole step (observed: 2 extra ~10 s Inception/NMT compiles and an
        18x wall-clock regression in the training loop)."""
        import jax
        from jax import lax

        return jax.tree.map(
            lambda p, s: lax.with_sharding_constraint(p, s),
            new_params, shardings)

    def _constrain_state(self, new_state):
        """Pin updated per-op state (e.g. BatchNorm running stats) to the
        sharding init() committed it with — replicated, or the stacked
        block-resident layout for registered group members — same retrace
        hazard as _constrain_params, via the state output."""
        import jax
        from jax import lax

        if not new_state:
            return new_state
        repl = self.machine.replicated()
        block_state = getattr(self, "_block_state", {})
        out = {}
        for name, st in new_state.items():
            bs = block_state.get(name)
            if bs:
                sh = self._block_sharding(bs)
                out[name] = {k: lax.with_sharding_constraint(v, sh[k])
                             for k, v in st.items()}
            else:
                out[name] = jax.tree.map(
                    lambda v: lax.with_sharding_constraint(v, repl), st)
        return out

    # ------------------------------------------------------------------
    # execution

    def _loss_op(self) -> Softmax:
        for op in reversed(self.layers):
            if getattr(op, "is_loss", False):
                return op
        raise ValueError("model has no loss (softmax) layer")

    # ------------------------------------------------------------------
    # apply-time fusion: RnnLinear -> SoftmaxDP collapses into the Pallas
    # fused projection+CE kernel (the (N, V) logits never reach HBM).
    # The reference launches these as two task graphs with the full logits
    # region between them (nmt/linear.cu -> nmt/softmax_data_parallel.cu).

    def _lm_head_fusion(self):
        cached = getattr(self, "_fusion_plan", None)
        if cached is not None:
            return cached
        from flexflow_tpu.ops.rnn_linear import RnnLinear
        from flexflow_tpu.ops.softmax_dp import SoftmaxDP

        plan: Dict[int, Any] = {}
        consumers: Dict[int, int] = {}
        for op in self.layers:
            for t in op.inputs:
                consumers[t.tid] = consumers.get(t.tid, 0) + 1
        index = {id(op): i for i, op in enumerate(self.layers)}
        for i, op in enumerate(self.layers):
            if not isinstance(op, SoftmaxDP):
                continue
            prod = op.inputs[0].producer
            if (isinstance(prod, RnnLinear)
                    and consumers.get(prod.output.tid) == 1
                    and id(prod) in index
                    and self._fusion_ok(prod)):
                plan[index[id(prod)]] = None   # folded away
                plan[i] = prod                 # loss op runs fused
        self._fusion_plan = plan
        return plan

    def _fusion_ok(self, lin) -> bool:
        """The fused head's whole rule: the backend (the gate every
        kernel shares, asked only of a model that has such a head, so a
        CNN never loads the kernels), then shapes, then placement."""
        from flexflow_tpu.ops.pallas import flash_enabled

        if not flash_enabled():
            return False
        pc_c, pn = lin.pc.dims
        b, s = lin.inputs[0].shape[0], lin.inputs[0].shape[1]
        d = lin.in_channels
        if d > 4096:
            # beyond it the backward's tile shrinks in 64 MiB of VMEM
            # (fused_ce._pick_tiles: 512 x 128 at d 5120, 256 x 128 at
            # 8192) until its sum through HBM no longer hides behind the
            # products; no such head has been timed: unfused
            return False
        if b * s < 2048:
            # small token counts (e.g. NMT's 640-token chunks) leave the
            # kernel weight-streaming-bound; XLA's single big GEMM wins
            # there (measured: 1583 vs 1638 img/s NMT, 177 vs 151 img/s LM)
            return False
        nd = self.machine.num_devices
        if pc_c == 1 and (nd == 1 or len(lin.pc.devices) == 1):
            return True
        # multi-device (incl. vocab TP): per-shard kernels under shard_map
        return (self.machine.is_canonical(lin.pc)
                and b % max(pn, 1) == 0
                and lin.out_channels % pc_c == 0)

    def _run_fused_lm_head(self, lin, lin_params, x, labels):
        import jax.numpy as jnp

        from flexflow_tpu.ops.pallas.fused_ce import (fused_linear_ce,
                                                      fused_linear_ce_partial)

        b_, s_, d_ = x.shape
        xf = x.reshape(b_ * s_, d_)
        labf = labels.reshape(-1)
        xf, w = lin.head_operands(lin_params, xf)
        bias = lin_params.get("bias")
        if bias is None:        # a head without bias: the kernel adds 0
            bias = jnp.zeros((lin.out_channels,), jnp.float32)
        pc_c = lin.pc.dims[0]
        if self.machine.num_devices > 1 and len(lin.pc.devices) > 1:
            from jax import lax
            from jax.sharding import PartitionSpec as P

            from flexflow_tpu.parallel.ring_attention import \
                unchecked_shard_map

            mesh = self.machine.mesh_for(lin.pc, lin.AXIS_NAMES)
            if pc_c == 1:
                nll = unchecked_shard_map(
                    fused_linear_ce, mesh,
                    (P("n", None), P(None, None), P(None), P("n")),
                    P("n"))(xf, w, bias, labf)
            else:
                # vocab TP: each c-shard runs the kernel over its vocab
                # slice with localized labels, then shards merge exactly —
                # lse by logsumexp, the correct-logit term by sum (a label
                # lives in exactly one shard; elsewhere nll_c == lse_c).
                # This is the reference's BWD2/replica reduction
                # (nmt/linear.cu:570-603) done on partial CE statistics
                # instead of materialized logits.
                v_local = lin.out_channels // pc_c

                def local(xl, wl, bl, labl):
                    lab_local = labl - lax.axis_index("c") * v_local
                    nll_c, lse_c = fused_linear_ce_partial(
                        xl, wl, bl, lab_local)
                    # stability shift only — gradients cancel through m,
                    # and pmax has no differentiation rule, so detach its
                    # input before the collective
                    m = lax.pmax(lax.stop_gradient(lse_c), "c")
                    # one fused all-reduce for both statistics
                    sums = lax.psum(
                        jnp.stack([jnp.exp(lse_c - m), lse_c - nll_c]),
                        "c")
                    lse_g = m + jnp.log(jnp.maximum(sums[0], 1e-30))
                    return lse_g - sums[1]

                nll = unchecked_shard_map(
                    local, mesh,
                    (P("n", None), P(None, "c"), P("c"), P("n")),
                    P("n"))(xf, w, bias, labf)
        else:
            nll = fused_linear_ce(xf, w, bias, labf)
        return nll.reshape(b_, s_)

    def _placement_schedule(self, exclude: frozenset):
        """Dataflow schedule with explicit-placement groups (cached per
        fusion-exclusion set).  Grouped pcs are recorded as THIS model's
        honored placements (scoped via machine.honored_placements, so a
        shared MachineModel does not suppress degraded-placement warnings
        across models)."""
        cached = getattr(self, "_sched_cache", None)
        if cached is not None and cached[0] == exclude:
            return cached[1]
        from flexflow_tpu.parallel.placement import (PlacementGroup,
                                                     plan_schedule)

        sched = plan_schedule(
            self.layers, self.machine.num_devices, exclude=exclude,
            overlap=getattr(self.config, "placed_overlap", "on") != "off")
        pcs = list(getattr(self, "_honored_pcs", ()))
        for entry in sched:
            if isinstance(entry, PlacementGroup):
                pcs.extend(m.pc for m in entry.members)
        self._honored_pcs = pcs
        self._sched_cache = (exclude, sched)
        if exclude == frozenset() and not hasattr(self, "_block_params"):
            self._block_params, self._block_state = \
                self._derive_block_params(sched)
        return sched

    def _derive_block_params(self, sched):
        """param_key -> {slot, dims, axes, strided, G} for params stored
        BLOCK-RESIDENT: stacked (G, ...) and sharded over the placement
        mesh's group axis, so a placed op's weights (and their gradients
        and optimizer state) physically live only on its device block.
        Without this the params enter the jit on the normalized canonical
        sharding and run_group re-stacks them ACROSS the group axis every
        step — on a two-tier machine that moves the full FC parameter
        footprint over DCN each iteration, erasing exactly the win the
        searched strategies claim (found by the round-4 compiled-HLO
        collective audit, tests/test_two_tier.py; the reference keeps
        non-shared weights on their op's GPUs, linear.cu:95-124).

        Eligible: members of block/stride groups (homogeneous AND, since
        the round-4 follow-up, heterogeneous — the hetero runner builds
        its group vector row-wise from the stacked leaves) whose
        param_key is used by exactly ONE layer (shared keys — the NMT
        SharedVariable pattern — may appear in several groups at
        different slots, which one stacked copy cannot serve) and is not
        a fused-LM-head candidate (that path consumes raw leaves)."""
        from flexflow_tpu.ops.rnn_linear import RnnLinear
        from flexflow_tpu.parallel.placement import PlacementGroup

        uses: Dict[str, int] = {}
        for op in self.layers:
            uses[op.param_key] = uses.get(op.param_key, 0) + 1
        out = {}
        state_out: Dict[str, dict] = {}
        for entry in sched:
            if not isinstance(entry, PlacementGroup):
                continue
            if entry.device_rows is not None:
                # set family (round 5, VERDICT r4 #3): params stored as
                # per-device POINT rows (N, *point_shape) sharded over
                # the flat mesh — each named device holds exactly the
                # param slice its grid point computes with, so an
                # irregular-set group no longer re-streams its member
                # params (across DCN on a two-tier machine) every step.
                # SOUNDNESS GATE: every leaf must be FULLY partitioned
                # across the grid (each nontrivial grid axis appears in
                # its spec).  A leaf replicated over some axis (e.g. a
                # batch-split linear's kernel) would store independent
                # per-point COPIES whose gradients never cross-sum on
                # the flat mesh (no live grid axes for the shard_map
                # transpose), silently diverging the replicas — the
                # block family is immune (its inner mesh axes are live
                # inside the group shard_map).
                for j, m in enumerate(entry.members):
                    if (uses.get(m.param_key) == 1 and m.param_specs()
                            and not isinstance(m, RnnLinear)
                            and _fully_partitioned(m)):
                        out[m.param_key] = {
                            "family": "set",
                            "row": tuple(entry.device_rows[j]),
                            "dims": m.pc.dims, "axes": m.AXIS_NAMES,
                            "N": self.machine.num_devices,
                            "specs": m.param_specs()}
                    # stateful set members (round 5: BatchNorm via its
                    # global-stats point_forward): state stored as
                    # per-device point rows like params.  No
                    # full-partitioning gate needed — state WRITES are
                    # deterministic per point (no gradient summing), so
                    # replicated rows stay consistent by construction
                    if m.init_state() and m.state_specs() is not None:
                        state_out[m.name] = {
                            "family": "set",
                            "row": tuple(entry.device_rows[j]),
                            "dims": m.pc.dims, "axes": m.AXIS_NAMES,
                            "N": self.machine.num_devices,
                            "specs": m.state_specs()}
                continue
            # homogeneous AND hetero groups qualify (round 4): the hetero
            # runner ravels each member's row slice into its group-vector
            # slot, which stays on the member's block
            for m, g in zip(entry.members, entry.slots):
                if (uses.get(m.param_key) == 1 and m.param_specs()
                        and not isinstance(m, RnnLinear)):
                    out[m.param_key] = {
                        "family": "block",
                        "slot": g, "dims": m.pc.dims,
                        "axes": m.AXIS_NAMES, "strided": entry.strided,
                        "G": entry.n_groups,
                        "specs": m.param_specs()}
                # state residency (round 5, VERDICT r4 #9): a stateful
                # member's state is stored the same stacked (G, ...)
                # way as its params — the runner merges rows by one-hot
                # masks and returns the member's row masked in place,
                # so no state byte crosses the group axis per step
                # (previously state entered replicated and was
                # re-stacked every step — the params gap at small
                # scale)
                if m.init_state() and m.state_specs() is not None:
                    state_out[m.name] = {
                        "family": "block",
                        "slot": g, "dims": m.pc.dims,
                        "axes": m.AXIS_NAMES, "strided": entry.strided,
                        "G": entry.n_groups,
                        "specs": m.state_specs()}
        return out, state_out

    def _block_sharding(self, bp):
        """{param name: NamedSharding} of one block-resident registry
        entry — the single source of truth for the stacked layout used
        by init() and _param_shardings().  Block/stride family: (G, ...)
        over the placement mesh's group axis.  Set family (round 5):
        (N, *point_shape) over the flat ``(_dev,)`` mesh — one point row
        per device."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        if bp.get("family") == "set":
            mesh = self.machine.flat_mesh()
            return {k: NamedSharding(mesh, P("_dev"))
                    for k in bp["specs"]}
        mesh = self.machine.placement_mesh(bp["dims"], bp["axes"],
                                           strided=bp["strided"])
        return {k: NamedSharding(mesh, P("_pg", *spec))
                for k, spec in bp["specs"].items()}

    def _member_params(self, params, op):
        """The op's param tree as ITS code expects it — block-resident
        keys are stored stacked (G, ...) (block/stride) or as per-device
        point rows (N, *point) (set family), so unplaced execution paths
        (single-op schedule entries, dump mode) reassemble the op's full
        tree."""
        p = params.get(op.param_key, {})
        bp = getattr(self, "_block_params", {}).get(op.param_key)
        if bp and p:
            import jax

            if bp.get("family") == "set":
                from flexflow_tpu.parallel.placement import _assemble

                sizes = dict(zip(bp["axes"], bp["dims"]))
                # master leaves (mixed precision) reuse the base spec
                p = {k: _assemble([l[d] for d in bp["row"]],
                                  bp["specs"][k] if k in bp["specs"]
                                  else bp["specs"][_opt_leaf_base(k)],
                                  sizes, bp["axes"], bp["dims"])
                     for k, l in p.items()}
            else:
                p = jax.tree.map(lambda l: l[bp["slot"]], p)
        return p

    def _member_state(self, state, op):
        """The op's state tree as ITS code expects it — block-resident
        state (see _derive_block_params) is stored stacked (G, ...)
        (block/stride) or as per-device point rows (set), so unplaced
        execution paths reassemble the op's tree."""
        st = state.get(op.name, {})
        bs = getattr(self, "_block_state", {}).get(op.name)
        if bs and st:
            import jax

            if bs.get("family") == "set":
                from flexflow_tpu.parallel.placement import _assemble

                sizes = dict(zip(bs["axes"], bs["dims"]))
                st = {k: _assemble([l[d] for d in bs["row"]],
                                   bs["specs"][k], sizes, bs["axes"],
                                   bs["dims"])
                      for k, l in st.items()}
            else:
                st = jax.tree.map(lambda l: l[bs["slot"]], st)
        return st

    def _restack_state(self, op, st):
        """Inverse of _member_state for the unplaced path: new state from
        a plain forward returns to the block-resident storage layout."""
        bs = getattr(self, "_block_state", {}).get(op.name)
        if not bs or not st:
            return st
        import jax.numpy as jnp

        if bs.get("family") == "set":
            return _point_rows(st, bs)
        G, slot = bs["G"], bs["slot"]
        return {k: jnp.zeros((G,) + v.shape, v.dtype).at[slot].set(v)
                for k, v in st.items()}

    def place_state(self, params, state, opt_state=None):
        """Place concrete FULL (plain-layout) param/state/opt trees onto
        this model's machine exactly as :meth:`init` would place freshly
        initialized ones — block-/set-resident registry entries land in
        their stacked storage, everything else on its op's sharding, state
        defaulting to replicated.  The landing half of elastic live-state
        migration (utils/elastic.py): the old model's member views
        reassemble per-op trees on host, this places them on the new
        (surviving) mesh.  Returns ``(params, state, opt_state)``."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        if self.machine.num_devices > 1:
            self._placement_schedule(frozenset())
        block = getattr(self, "_block_params", {})
        block_state = getattr(self, "_block_state", {})

        def shard_of(sh, k):
            # optimizer master leaves (<leaf>__master) inherit the BASE
            # param leaf's sharding — shardings are dtype-agnostic
            return sh[k] if k in sh else sh[_opt_leaf_base(k)]

        def stack(tree, slot, G, sh):
            return {k: jax.device_put(
                jnp.zeros((G,) + tuple(np.shape(v)),
                          np.asarray(v).dtype).at[slot].set(v),
                shard_of(sh, k))
                for k, v in tree.items()}

        def place_keyed(tree):
            out = {}
            for op in self.layers:
                key = op.param_key
                if key not in (tree or {}) or key in out:
                    continue
                p = tree[key]
                bp = block.get(key)
                if p and bp and bp.get("family") == "set":
                    sh = self._block_sharding(bp)
                    out[key] = {k: jax.device_put(v, shard_of(sh, k))
                                for k, v in _point_rows(p, bp).items()}
                elif p and bp:
                    out[key] = stack(p, bp["slot"], bp["G"],
                                     self._block_sharding(bp))
                elif p:
                    with self._honored_ctx():
                        sh = op.param_shardings(self.machine)
                    out[key] = {k: jax.device_put(
                        v, sh.get(k, sh.get(_opt_leaf_base(k))))
                        if (k in sh or _opt_leaf_base(k) in sh)
                        else jax.device_put(v)
                        for k, v in p.items()}
            return out

        placed_p = place_keyed(params)
        placed_o = place_keyed(opt_state) if opt_state else {}
        placed_s: Dict[str, Dict] = {}
        repl = self.machine.replicated() if state else None
        for op in self.layers:
            nm = op.name
            if nm not in (state or {}) or nm in placed_s:
                continue
            st = state[nm]
            bs = block_state.get(nm)
            if st and bs and bs.get("family") == "set":
                sh = self._block_sharding(bs)
                placed_s[nm] = {k: jax.device_put(v, sh[k])
                                for k, v in _point_rows(st, bs).items()}
            elif st and bs:
                placed_s[nm] = stack(st, bs["slot"], bs["G"],
                                     self._block_sharding(bs))
            elif st:
                placed_s[nm] = {k: jax.device_put(jnp.asarray(v), repl)
                                for k, v in st.items()}
        return placed_p, placed_s, placed_o

    def _honored_ctx(self):
        return self.machine.honored_placements(
            getattr(self, "_honored_pcs", ()))

    def _plan(self, train: bool):
        """(fusion plan, schedule) for one apply — the ONE gating shared by
        apply() and _apply(), so the pre-planned honored set always matches
        the schedule actually executed (both underlying planners cache)."""
        dump = self.config.print_intermediates
        with obs.span("ff:entry.graph_plan", ops=len(self.layers)):
            fusion = self._lm_head_fusion() if (train and not dump) else {}
            if self.machine.num_devices > 1 and not dump:
                schedule = self._placement_schedule(frozenset(fusion))
            else:
                schedule = range(len(self.layers))
        return fusion, schedule

    def _regrid_plan_for(self, fusion, schedule):
        """The whole-graph :class:`~flexflow_tpu.parallel.regrid.RegridPlan`
        for this (fusion, schedule) — every producer->consumer reshard
        edge resolved, coalesced, and cost-priced ONCE instead of
        re-derived per input per op on every trace (parallel/regrid.py).
        Cached per fusion-exclusion set; None on single-device machines,
        in dump mode, or when ``config.regrid_planner`` is "off" (the
        legacy per-trace path, kept for the bit-identical equivalence
        tests)."""
        if self.machine.num_devices <= 1 or self.config.print_intermediates:
            return None
        if getattr(self.config, "regrid_planner", "on") == "off":
            return None
        key = frozenset(fusion)
        cache = getattr(self, "_regrid_plans", None)
        if cache is None:
            cache = self._regrid_plans = {}
        if key not in cache:
            from flexflow_tpu.parallel.regrid import build_regrid_plan

            with obs.span("ff:entry.regrid_plan",
                          ops=len(self.layers)) as sp:
                cache[key] = build_regrid_plan(self, fusion, schedule)
                sp.args["edges"] = len(cache[key].edges)
        return cache[key]

    def regrid_plan_summary(self, train: bool = True):
        """The active regrid plan's accounting (edges / hops / sharding
        constraints before vs after coalescing, predicted transfer cost
        and bytes) — the ``regrid_plan`` obs record body; None when the
        planner is inactive."""
        fusion, schedule = self._plan(train)
        plan = self._regrid_plan_for(fusion, schedule)
        return plan.summary() if plan is not None else None

    def apply(self, params, state, inputs: Dict[int, Any], train: bool):
        """Run the DAG. ``inputs`` maps input-Tensor tid -> array.
        Returns (tensor-values dict, new_state)."""
        # Plan the schedule _apply will use BEFORE snapshotting the honored
        # set, so a placement group that exists only under this fusion
        # exclusion is already marked honored when tracing starts (round-2
        # ADVICE: the late plan drew a spurious one-time "placement not
        # honored" warning from run_group's output sharding constraint).
        self._plan(train)
        with self._honored_ctx():
            return self._apply(params, state, inputs, train)

    @contextlib.contextmanager
    def _op_scope(self, op, xs, block=None):
        """``op``'s ``jax.named_scope`` (every device operation carries
        its operator's name in its ``op_name`` metadata; obs/optrace.py
        reads it back) and, where an input is a tracer, an
        ``ff:entry.trace_op`` span: the Python of a jitted function runs
        only while JAX traces it, so the span times the tracing of one
        operator and a running step never meets it.  The raw records are
        bounded a name, so the self seconds are also summed by the
        operator's class (``entry.trace_op_s.<class>``)."""
        import jax

        with jax.named_scope(op.name):
            if not any(isinstance(x, jax.core.Tracer) for x in xs):
                yield
                return
            kind = type(op).__name__
            where = {} if block is None else {"block": block}
            with obs.span("ff:entry.trace_op", op=op.name, kind=kind,
                          **where) as sp:
                yield
            obs.count("entry.trace_op_s." + kind, sp.self_s)

    def _apply(self, params, state, inputs: Dict[int, Any], train: bool):
        import jax
        from jax import lax

        from flexflow_tpu.parallel.placement import (PlacementGroup,
                                                     run_group)

        multi = self.machine.num_devices > 1
        dump = self.config.print_intermediates
        fusion, schedule = self._plan(train)
        # planned regrids (parallel/regrid.py): every reshard edge was
        # resolved once at plan time; _apply only looks plans up by
        # (op name, input index) and reuses fan-out reshards via rcache.
        # plan None -> the legacy per-trace path below re-derives edges.
        plan = self._regrid_plan_for(fusion, schedule)
        rcache: Dict[Any, Any] = {}
        values: Dict[int, Any] = dict(inputs)
        # consumer reads go through ``take``: multi-consumer tensors hand
        # each consumer its own grad_fanout alias so the branch
        # cotangents re-join as ONE balanced tree sum (ops/fanout.py)
        # instead of the chained add_any fusions the profile prices
        take = self._make_value_reader(values, fusion, schedule, train)
        new_state: Dict[str, Dict] = {}
        # tid -> global-mesh entry tuple of each produced value, for
        # decomposing producer->consumer regrids (see _regrid_inputs);
        # model inputs arrive batch-sharded over the whole machine (the
        # loaders' convention, data/synthetic.py).  Only tracked on the
        # legacy path — the planner mirrored it at plan time.
        specs: Dict[int, Any] = {}
        if multi and plan is None:
            dp = ParallelConfig.data_parallel(1, self.machine.num_devices)
            from jax.sharding import PartitionSpec as P

            for t in self._inputs:
                specs[t.tid] = self.machine.global_entries(
                    dp, ("n",), P("n"), rank=t.ndim)
        recomputed = self._recompute_plan(fusion) if train else {}
        if recomputed and (multi or dump):
            raise ValueError(
                "a model class that recomputes its blocks in the backward "
                "pass runs on one device, without print_intermediates")
        inside: set = set()
        if recomputed:
            # a level: the bytes one traced step's blocks keep
            obs.count("runtime.kept_bytes", 0, level=True)
        for entry in schedule:
            if recomputed and entry in inside:
                continue        # ran with the first operator of its block
            if recomputed and entry in recomputed:
                blk = recomputed[entry]
                self._run_recomputed(blk, params, state, values, take,
                                     new_state)
                inside.update(blk["ops"])
                continue
            if isinstance(entry, PlacementGroup):
                block = getattr(self, "_block_params", {})
                block_state = getattr(self, "_block_state", {})
                pre = [_registry_match(block.get(m.param_key), m, entry,
                                       j, g)
                       for j, (m, g) in
                       enumerate(zip(entry.members, entry.slots))]
                spre = [_registry_match(block_state.get(m.name), m,
                                        entry, j, g)
                        for j, (m, g) in
                        enumerate(zip(entry.members, entry.slots))]
                if plan is not None:
                    member_inputs = [
                        [plan.apply(m.name, i, take(t.tid), rcache)
                         for i, t in enumerate(m.inputs)]
                        for m in entry.members]
                else:
                    member_inputs = [
                        self._regrid_group_inputs(
                            entry, m, [take(t.tid) for t in m.inputs],
                            specs) if multi else
                        [take(t.tid) for t in m.inputs]
                        for m in entry.members]
                outs_by_member, states_by_member = run_group(
                    self.machine, entry,
                    [params.get(m.param_key, {}) if pre[j] else
                     self._member_params(params, m)
                     for j, m in enumerate(entry.members)],
                    member_inputs, train,
                    [state.get(m.name, {}) if spre[j] else
                     self._member_state(state, m)
                     for j, m in enumerate(entry.members)],
                    prestacked=pre, state_prestacked=spre)
                for m, outs, st in zip(entry.members, outs_by_member,
                                       states_by_member):
                    for t, y, spec in zip(m.all_outputs(), outs,
                                          m.output_specs()):
                        values[t.tid] = y
                        # record the exit layout (run_group constrained
                        # each member output to its pc's normalized
                        # sharding, which lives on the global mesh when
                        # the grid decomposes) so downstream
                        # _regrid_inputs can decompose the jump into
                        # single-axis hops instead of letting GSPMD
                        # full-rematerialize it (round 5)
                        if multi and plan is None and spec is not None:
                            specs[t.tid] = self.machine.global_entries(
                                m.pc, m.AXIS_NAMES, spec, rank=t.ndim)
                    if st:
                        new_state[m.name] = st
                continue
            i = entry
            op = self.layers[i]
            if i in fusion:
                lin = fusion[i]
                if lin is None:
                    continue  # projection folded into its loss op
                x, labels = take(lin.inputs[0].tid), take(
                    op.labels_tensor.tid)
                with self._op_scope(lin, (x, labels)), \
                        jax.named_scope(op.name):
                    values[op.output.tid] = self._run_fused_lm_head(
                        lin, params.get(lin.param_key, {}), x, labels)
                continue
            xs = [take(t.tid) for t in op.inputs]
            if multi and plan is not None:
                xs = [plan.apply(op.name, i, x, rcache)
                      for i, x in enumerate(xs)]
            elif multi:
                xs = self._regrid_inputs(op, xs, specs)
            with self._op_scope(op, xs):
                res, st = op.forward(self._member_params(params, op),
                                     self._member_state(state, op), xs,
                                     train)
                if st:
                    st = self._restack_state(op, st)
            ys = res if isinstance(res, tuple) else (res,)
            for t, y, spec in zip(op.all_outputs(), ys, op.output_specs()):
                if multi and spec is not None:
                    y = lax.with_sharding_constraint(
                        y, self.machine.sharding(op.pc, op.AXIS_NAMES, spec))
                    if plan is None:
                        specs[t.tid] = self.machine.global_entries(
                            op.pc, op.AXIS_NAMES, spec, rank=t.ndim)
                if dump:
                    print_tensor(f"{op.name}/{t.name or 'out'}", y)
                values[t.tid] = y
            if st:
                new_state[op.name] = st
        return values, new_state

    def _recompute_plan(self, fusion) -> Dict[int, Dict]:
        """{first layer index: block} for the blocks the model class
        recomputes in the backward pass (``recompute_blocks``: ranges of
        layer indices, fixed where the class builds its graph; no model
        without the attribute takes any of this).  A block saves only
        the values that cross its boundary: ``inputs`` are the tids it
        reads from outside, in order of first use, and ``outputs`` those
        it produces that a later operator reads."""
        blocks = getattr(self, "recompute_blocks", ())
        key = frozenset(fusion)
        cached = getattr(self, "_recompute_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        plan: Dict[int, Dict] = {}
        for rng in blocks:
            idx = list(rng)
            if any(i in fusion for i in idx):
                raise ValueError("a recomputed block may not hold the "
                                 "fused vocabulary head")
            ops = [self.layers[i] for i in idx]
            made = {t.tid for op in ops for t in op.all_outputs()}
            inputs = list(dict.fromkeys(
                t.tid for op in ops for t in op.inputs
                if t.tid not in made))
            later = {t.tid for i, op in enumerate(self.layers)
                     if i not in rng for t in op.inputs}
            outputs = [t.tid for op in ops for t in op.all_outputs()
                       if t.tid in later]
            plan[idx[0]] = {"block": len(plan), "ops": idx,
                            "inputs": inputs, "outputs": outputs}
        self._recompute_cache = (key, plan)
        return plan

    def _run_recomputed(self, blk, params, state, values, take, new_state):
        """Run one block under ``jax.checkpoint``: its forward keeps the
        block's inputs and the results the kernels name
        (``ops/pallas.KEPT_RESULTS``: the flash forward's ``out`` and
        ``lse``, which its backward kernels read and only the kernel
        could make again), and the backward pass runs the block's other
        operators once more (each under its own name, so the operator
        table still charges every instruction) before differentiating
        them.  A kernel's results are kept where they cost less to hold
        than to make: 68 MB against 6.84 ms a Moonlight layer (PERF.md
        section 6, PR 29)."""
        import jax

        from flexflow_tpu.ops.pallas import KEPT_RESULTS

        ops = [self.layers[i] for i in blk["ops"]]
        obs.count("runtime.recomputed_blocks")
        named = jax.checkpoint_policies.save_only_these_names(*KEPT_RESULTS)

        def keep(prim, *avals, **params):
            # JAX asks once a value while it builds the block's backward
            kept = named(prim, *avals, **params)
            if kept:
                obs.count("runtime.kept_results")
                obs.count("runtime.kept_bytes",
                          avals[0].size * avals[0].dtype.itemsize)
            return kept

        def body(p, s, xs):
            vals = dict(zip(blk["inputs"], xs))
            st_out = {}
            for op in ops:
                op_xs = [vals[t.tid] for t in op.inputs]
                with self._op_scope(op, op_xs, blk["block"]):
                    res, st = op.forward(
                        self._member_params(p, op),
                        self._member_state(s, op), op_xs, True)
                    if st:
                        st = self._restack_state(op, st)
                ys = res if isinstance(res, tuple) else (res,)
                for t, y in zip(op.all_outputs(), ys):
                    vals[t.tid] = y
                if st:
                    st_out[op.name] = st
            return [vals[t] for t in blk["outputs"]], st_out

        # ``jax.checkpoint`` traces its body wherever it is called: what
        # the span holds beyond its operators is JAX's own work on the
        # block (the policy's questions, the partial evaluation)
        with obs.span("ff:entry.trace_block", block=blk["block"],
                      ops=len(ops)) as sp:
            outs, st = jax.checkpoint(body, policy=keep)(
                {op.param_key: params[op.param_key] for op in ops
                 if op.param_key in params},
                {op.name: state[op.name] for op in ops
                 if op.name in state},
                [take(t) for t in blk["inputs"]])
        obs.count(f"entry.trace_block_s.{blk['block']}", sp.seconds)
        values.update(zip(blk["outputs"], outs))
        new_state.update(st)

    def _consumer_counts(self, fusion, schedule):
        """How many times _apply reads each tid, mirroring its control
        flow exactly (placement groups, folded lm-head fusions, plain
        ops) — the fan width of _make_value_reader.  Static per plan."""
        from collections import Counter

        from flexflow_tpu.parallel.placement import PlacementGroup

        counts: Counter = Counter()
        recomputed = self._recompute_plan(fusion)
        inside = {i for blk in recomputed.values() for i in blk["ops"]}
        for entry in schedule:
            if recomputed and entry in recomputed:
                # a block reads each of its inputs once
                counts.update(recomputed[entry]["inputs"])
            if recomputed and entry in inside:
                continue
            if isinstance(entry, PlacementGroup):
                for m in entry.members:
                    for t in m.inputs:
                        counts[t.tid] += 1
                continue
            op = self.layers[entry]
            if entry in fusion:
                lin = fusion[entry]
                if lin is not None:
                    counts[lin.inputs[0].tid] += 1
                    counts[op.labels_tensor.tid] += 1
                continue
            for t in op.inputs:
                counts[t.tid] += 1
        return counts

    def _make_value_reader(self, values, fusion, schedule, train):
        """The consumer-read accessor for _apply.  With
        config.grad_fanout = "tree" (and a training trace — eval has no
        cotangents to accumulate), a tensor with n >= 2 consumers is
        read as n grad_fanout aliases, one popped per consumer, so the
        branch cotangents re-join as one balanced n-ary sum
        (ops/fanout.py) instead of JAX's scattered pairwise add_any
        chain.  Floating arrays only; everything else reads raw."""
        if not train or getattr(self.config, "grad_fanout", "tree") \
                == "off":
            return values.__getitem__
        counts = self._consumer_counts(fusion, schedule)
        if not any(n >= 2 for n in counts.values()):
            return values.__getitem__
        import jax.numpy as jnp

        from flexflow_tpu.ops.fanout import grad_fanout

        pending: Dict[int, list] = {}

        def take(tid):
            n = counts.get(tid, 0)
            if n < 2:
                return values[tid]
            q = pending.get(tid)
            if q is None:
                v = values[tid]
                if not (hasattr(v, "dtype")
                        and jnp.issubdtype(v.dtype, jnp.floating)):
                    return v
                q = pending[tid] = list(grad_fanout(v, n))
            return q.pop()

        return take

    def _regrid_group_inputs(self, entry, m, xs, specs):
        """LEGACY per-trace resharding for a placement-group member's
        inputs (round 5) — only reached with ``regrid_planner=off``; the
        planned path applies the pre-resolved ``RegridPlan`` edges in
        ``_apply`` instead.  Group inputs bypass ``_regrid_inputs`` and
        meet the group shard_map's in_specs directly; when the producer's
        layout is known on the global mesh, walk there in single-axis
        hops exactly like the single-op path — a spatial-grid producer
        feeding a batch-grid group otherwise triggers GSPMD's
        involuntary full rematerialization at the shard_map boundary.
        Set-family members consume REPLICATED operands (the per-device
        dispatch contract), so their target is the all-axes-dropped
        layout."""
        from jax import lax

        if entry.device_rows is not None:
            targets = [tuple(() for _ in range(t.ndim)) for t in m.inputs]
        else:
            ins = m.input_specs()
            if ins is None:
                return xs
            targets = [self.machine.global_entries(m.pc, m.AXIS_NAMES,
                                                   spec, rank=t.ndim)
                       for spec, t in zip(ins, m.inputs)]
        import jax

        out = []
        for i, (x, t, dst) in enumerate(zip(xs, m.inputs, targets)):
            src = specs.get(t.tid)
            if dst is None or src is None or dst == src:
                out.append(x)
                continue
            with jax.named_scope(f"ff_regrid.{m.name}.{i}"):
                for step in self.machine.regrid_steps(src, dst) or []:
                    x = lax.with_sharding_constraint(
                        x, self.machine.entries_sharding(step))
                x = lax.with_sharding_constraint(
                    x, self.machine.entries_sharding(dst))
            out.append(x)
        return out

    def _regrid_inputs(self, op, xs, specs):
        """LEGACY per-trace resharding of ``op``'s inputs to the layout
        its compute wants, as a chain of single-mesh-axis hops
        (MachineModel.regrid_steps) from each producer's recorded layout
        — only reached with ``regrid_planner=off``; the planned path
        applies pre-resolved ``RegridPlan`` edges in ``_apply``.  GSPMD
        lowers each hop as an all-to-all / all-gather / slice where the
        combined jump would trigger involuntary full rematerialization.
        The reference relies on Legion for the same producer/consumer
        repartitioning (conv_2d.cu:171-208)."""
        from jax import lax

        want = op.regrid_input_specs()
        if want is None:
            return xs
        import jax

        out = []
        for i, (x, t, spec) in enumerate(zip(xs, op.inputs, want)):
            if spec is None:
                out.append(x)
                continue
            dst = self.machine.global_entries(op.pc, op.AXIS_NAMES, spec,
                                              rank=t.ndim)
            src = specs.get(t.tid)
            if dst is None or dst == src:
                out.append(x)
                continue
            with jax.named_scope(f"ff_regrid.{op.name}.{i}"):
                if src is not None:
                    for step in self.machine.regrid_steps(src, dst) or []:
                        x = lax.with_sharding_constraint(
                            x, self.machine.entries_sharding(step))
                else:
                    # unknown producer layout (a placement-group exit
                    # whose grid does not decompose onto the global
                    # mesh): GSPMD's only general lowering to ``dst`` is
                    # replicate-then-slice — state the waypoint so the
                    # identical program compiles without the
                    # involuntary-remat warning
                    x = lax.with_sharding_constraint(
                        x, self.machine.replicated())
                x = lax.with_sharding_constraint(
                    x, self.machine.entries_sharding(dst))
            out.append(x)
        return out

    def loss_fn(self, params, state, image, labels, train: bool = True):
        loss_op = self._loss_op()
        inputs = {self._inputs[0].tid: image}
        values, new_state = self.apply(params, state, inputs, train)
        out = values[loss_op.output.tid]
        with self._op_scope(loss_op, (out, labels)):
            loss = loss_op.loss(out, labels)
        return loss, new_state

    def _publish_state_counters(self, state) -> None:
        """Counters that operators keep in their ``state`` (an expert
        layer's load and the pairs its buffer could not take), published
        through ``obs.count`` at a point where ``fit`` has just waited
        for the step that wrote them: the step itself gains no
        synchronisation.  Over the operators a name's values are merged
        as the operator says (``max`` or ``sum``)."""
        merged: Dict[str, float] = {}
        for op in self.layers:
            read = getattr(op, "state_counters", None)
            if read is None or op.name not in (state or {}):
                continue
            for name, (value, how) in read(
                    self._member_state(state, op)).items():
                merged[name] = value if name not in merged else (
                    max(merged[name], value) if how == "max"
                    else merged[name] + value)
        for name, value in merged.items():
            obs.count(name, value, level=True)

    def _donate(self, argnums):
        """donate_argnums gated by config.donate — "off" is the A/B arm
        of the donation bit-identity contract (tests/test_donation.py):
        aliasing an input buffer to an output must never change a bit of
        the computed update, only where the update lands."""
        return argnums if getattr(self.config, "donate", "on") != "off" \
            else ()

    def _jit_step(self, train_step):
        """The donated, jitted train step ``_make_step`` returns,
        noted in ``obs`` as the program a device trace of training shows
        (:class:`_TrainStep`: it remembers what it first ran on, which
        ``operator_table()`` lowers it for again)."""
        import jax

        # the HLO module is named for this function, and a module's name
        # is part of a compile-cache key where the operators' names
        # (metadata) are not: under a name no program from before the
        # scopes compiled, no cache directory holds this step without them
        train_step.__name__ = "ff_train_step"
        obs.note_program("train_step", self)
        return _TrainStep(self, jax.jit(
            train_step, donate_argnums=self._donate((0, 1, 2))))

    def make_train_step(self):
        """Jitted full training iteration (forward+backward+update) under
        SGD with momentum and weight decay: ``v = mu*v + g + wd*p;
        p -= lr*v``."""
        cfg = self.config
        lr, wd, mu = cfg.learning_rate, cfg.weight_decay, cfg.momentum

        def momentum(p, g, v):
            v = mu * v + g + wd * p
            return p - lr * v, v

        return self._make_step(momentum)

    def make_sgd_step(self, lr: float):
        """Plain-SGD train step over ``self.loss_fn(params, state, *batch)``
        — shared by the RNN and transformer subclasses (their reference
        counterparts apply bare rate*grad updates, nmt/rnn.cu:684-702)."""
        return self._make_step(lambda p, g: (p - lr * g,))

    def _make_step(self, rule):
        """The one train step: the floating batch inputs cast to the
        compute dtype, the loss's value and gradient, and ``rule(p, g,
        *carried) -> (p', *carried')`` applied leaf by leaf inside
        ``jax.named_scope(UPDATE_SCOPE)``, jitted by ``_jit_step``.  What
        the rule carries for a leaf (momentum's velocity) rides in the
        optimizer state under the leaf's name.  In mixed precision the
        loss reads compute-dtype casts of the stored params and a leaf
        with a float32 master (``<leaf>__master``) is updated there: the
        rule runs on the master against the float32 gradient and the
        stored leaf is re-cast from the new master, so update math never
        accumulates in the storage dtype; a leaf without one (not
        floating) is updated in its own dtype."""
        import jax
        import jax.numpy as jnp

        cdtype = self.config.compute_dtype
        mixed = self._mixed_precision()

        def train_step(params, state, opt_state, *batch):
            batch = _cast_floats(batch, cdtype)

            def lf(p):
                return self.loss_fn(_cast_floats(p, cdtype) if mixed else p,
                                    state, *batch, train=True)

            (loss, new_state), grads = jax.value_and_grad(
                lf, has_aux=True)(params)
            new_params, new_opt = {}, {}
            with jax.named_scope(UPDATE_SCOPE):
                for key, sub in params.items():
                    np_, no_ = {}, {}
                    osub = (opt_state or {}).get(key, {})
                    for k, p in sub.items():
                        g, mk = grads[key][k], k + _MASTER_SUFFIX
                        carried = (osub[k],) if k in osub else ()
                        if mk in osub:
                            m, *carried = rule(osub[mk],
                                               g.astype(jnp.float32),
                                               *carried)
                            np_[k], no_[mk] = m.astype(p.dtype), m
                        else:
                            np_[k], *carried = rule(p, g, *carried)
                        if carried:
                            (no_[k],) = carried
                    new_params[key] = np_
                    if no_:
                        new_opt[key] = no_
                psh = self._param_shardings(new_params)
                new_params = self._constrain_params(new_params, psh)
                if new_opt:
                    new_opt = self._constrain_params(
                        new_opt, self._opt_shardings(new_opt, psh))
            return new_params, self._constrain_state(new_state), \
                new_opt or opt_state, loss

        return self._jit_step(train_step)

    @staticmethod
    def _lower_step(step, params, state, opt_state, batch):
        import jax

        abstract = [jax.ShapeDtypeStruct(b.shape, b.dtype,
                                         sharding=getattr(b, "sharding",
                                                          None))
                    for b in batch]
        return step.lower(params, state, opt_state, *abstract)

    def abstract_train_state(self):
        """(params, state, opt_state) as sharding-annotated
        ShapeDtypeStructs — the avals ``init()`` would produce (same
        traversal, ``abstract=True``) with nothing materialized."""
        with obs.span("ff:entry.abstract_state", ops=len(self.layers)):
            return self._abstract_train_state()

    def _abstract_train_state(self):
        import jax

        params, state = self.init(abstract=True)
        # honor subclass init_opt_state overrides (e.g. plain-SGD models
        # return None); re-attach param shardings when the trees mirror
        opt_state = jax.eval_shape(self.init_opt_state, params)
        try:
            opt_state = jax.tree.map(
                lambda o, p: jax.ShapeDtypeStruct(o.shape, o.dtype,
                                                  sharding=p.sharding),
                opt_state, params)
        except ValueError:
            # mixed-precision opt trees carry extra __master leaves, so
            # the structures diverge — map each opt leaf to its BASE
            # param leaf's sharding instead (masters mirror their param)
            if isinstance(opt_state, dict):
                opt_state = {
                    key: {k: jax.ShapeDtypeStruct(
                        o.shape, o.dtype,
                        sharding=params[key][_opt_leaf_base(k)].sharding)
                        for k, o in sub.items()}
                    for key, sub in opt_state.items()}
        return params, state, opt_state

    def compile_train_step(self, *batch):
        """Compile (but do not run) the full training step — the
        DISABLE_COMPUTATION analog (ops.h:19).  ``batch`` supplies the data
        avals (arrays or ShapeDtypeStructs).  Nothing is materialized: the
        train state enters lowering as sharded avals, so arbitrarily large
        models compile-check on any machine.  Returns the compiled
        executable (``.cost_analysis()``, ``.memory_analysis()``,
        ``.as_text()`` for inspection)."""
        params, state, opt_state = self.abstract_train_state()
        return self._lower_step(self.make_train_step(), params, state,
                                opt_state, batch).compile()

    def operator_table(self, *batch):
        """{instruction name: (operator, pass)} of the compiled train
        step (obs/optrace.py): which operator's forward or backward, the
        update, or which regrid each device operation of a trace belongs
        to.  Without ``batch`` it is the table of the step that ran: the
        very jitted object, lowered again for the shapes, dtypes and
        shardings of its first call (found in jit's and the compile
        cache).  With ``batch`` (data avals as for
        :meth:`compile_train_step`) it compiles a fresh step for them."""
        from flexflow_tpu.obs import optrace

        if batch:
            compiled = self.compile_train_step(*batch)
        else:
            step = getattr(self, "_ran_step", None)
            if step is None:
                raise ValueError("operator_table() needs the batch's "
                                 "shapes: no train step has run yet")
            compiled = step.lower(*step.first_call).compile()
        names = {op.name for op in self.layers}
        table = optrace.operator_table(compiled.as_text(), names)
        named = {operator for operator, _ in table.values()}
        unnamed = sorted(op.name for op in self.layers
                         if op.name not in named and not op.IS_VIEW)
        if unnamed:
            # JAX leaves metadata out of a cache key: a directory written
            # by a program with other scopes serves its names, or none
            raise ValueError(
                f"the compiled step names {len(names & named)} of "
                f"{len(names)} operators and not {unnamed[:5]}: it came "
                f"from a compile cache written without this program's "
                f"scopes")
        return table

    def make_eval_step(self):
        import jax
        import jax.numpy as jnp

        loss_op = self._loss_op()

        def eval_step(params, state, image, labels):
            image = image.astype(self.config.compute_dtype)
            if self._mixed_precision():
                params = _cast_floats(params, self.config.compute_dtype)
            inputs = {self._inputs[0].tid: image}
            values, _ = self.apply(params, state, inputs, train=False)
            log_probs = values[loss_op.output.tid]
            loss = loss_op.loss(log_probs, labels)
            acc = jnp.mean((jnp.argmax(log_probs, axis=-1) == labels)
                           .astype("float32"))
            return loss, acc

        return jax.jit(eval_step)

    def make_predict_step(self, output_tids=None):
        """Jitted forward-only inference step — the serving path
        (flexflow_tpu/serve/).  Differs from :meth:`make_eval_step`,
        which exists for mid-training validation: no labels, no loss, no
        accuracy — the step returns raw output tensors; no optimizer
        state anywhere near the signature; and the BATCH arguments are
        donated (a request's activations die with its reply) while
        params/state are NOT (they persist across every request the
        engine serves).  Dispatch is the exact training ``apply()``
        path — strategies, placed/grouped execution, regrid — so a
        searched serving strategy runs the same program the latency
        objective priced.

        ``output_tids``: tensor ids to return (in order); default is the
        loss op's output (log-probs).  The serve engine passes the
        softmax tid plus per-layer attention-input tids so the KV cache
        can be filled from the same forward.  Positional ``batch`` args
        align with ``self._inputs`` (the transformer's labels input is
        fed zeros by the engine — the softmax op reads it but only
        ``loss()`` consumes it, and serving never calls ``loss()``)."""
        import jax

        tids = tuple(output_tids) if output_tids is not None \
            else (self._loss_op().output.tid,)
        cdtype = self.config.compute_dtype

        def predict_step(params, state, *batch):
            if self._mixed_precision():
                params = _cast_floats(params, cdtype)
            inputs = {t.tid: b for t, b in zip(
                self._inputs, _cast_floats(batch, cdtype))}
            values, _ = self.apply(params, state, inputs, train=False)
            return tuple(values[tid] for tid in tids)

        n_data = len(self._inputs)
        return jax.jit(
            predict_step,
            donate_argnums=self._donate(tuple(range(2, 2 + n_data))))

    # ------------------------------------------------------------------
    # training loop (cnn.cc:110-128 parity: timed loop printing images/s)

    def fit(self, data_iter, num_iterations: Optional[int] = None,
            warmup: int = 1, log=print, rebuild=None):
        """Train for ``num_iterations``.  ``rebuild(config, machine)`` is
        the optional model factory elastic recovery uses to reconstruct
        the graph on a surviving mesh after permanent device loss
        (``--elastic``, utils/elastic.py) — the drivers pass their
        builder; without it a device loss is fatal."""
        from flexflow_tpu.utils import elastic as _elastic
        from flexflow_tpu.utils import faultinject

        num_iterations = num_iterations or self.config.num_iterations
        # run telemetry (obs subsystem): a live JSONL sink when
        # config.obs_dir is set, else the shared no-op NULL — the step
        # loop below pays one predicate check per iteration when disabled
        olog = obs.from_config(
            self.config, surface="fit",
            meta={"model": type(self).__name__,
                  "layers": len(self.layers),
                  "devices": self.machine.num_devices,
                  # whose peaks a utilization read off this stream is a
                  # fraction of (obs/budget.mfu_waterfall)
                  "platform": self.machine.devices[0].platform,
                  "device_kind": self.machine.devices[0].device_kind,
                  "batch_size": self.config.batch_size,
                  "iterations": num_iterations,
                  "compute_dtype": self.config.compute_dtype,
                  "strategy_ops": len(self.config.strategies)})
        # deterministic fault injection (utils/faultinject.py): installed
        # process-globally for the run so background data threads see the
        # same schedule; the restore callable is idempotent/re-entrant —
        # the drain path and the error path can both reach it (a leaked
        # injector would fire into the next run)
        inj = faultinject.from_config(self.config, olog=olog)
        restore_inj = faultinject.install_scoped(inj) if inj.enabled \
            else None
        # graceful drain (utils/elastic.py): SIGTERM/SIGINT set a flag
        # the loop reads at its existing boundaries; handlers live only
        # inside fit and are restored on every exit path
        drain = {"requested": False, "signum": None}
        restore_sig = _elastic.install_drain_handler(drain, log)
        try:
            # elastic outer loop (utils/elastic.py): each detected
            # permanent device loss shrinks onto the surviving mesh and
            # CONTINUES the same logical run on the rebuilt model —
            # prior losses are carried so callers see one history.
            # After a shrink, regrow_ctx tracks the out-of-service
            # devices; K consecutive healthy boundary probes raise
            # DeviceReturnDetected and the run grows back (at most
            # --max-regrows times).
            model = self
            carry = None
            resizes = 0
            resize_dirs = {"shrink": 0, "grow": 0}
            regrow_ctx = None
            regrows = 0
            max_regrows = max(int(getattr(self.config, "max_regrows", 1)
                                  or 0), 0)
            prior_losses: List[float] = []
            while True:
                try:
                    out = model._fit(
                        data_iter, num_iterations, warmup, log, olog,
                        inj, elastic_resume=carry,
                        elastic_resizes=resizes,
                        elastic_regrow=(regrow_ctx
                                        if regrows < max_regrows
                                        else None),
                        resize_dirs=resize_dirs, drain=drain)
                    if prior_losses:
                        out["loss"] = prior_losses + out["loss"]
                    out["elastic_resizes"] = resizes
                    out["devices"] = model.machine.num_devices
                    return out
                except _elastic.DeviceLossDetected as sig:
                    # capture the dead device objects + pre-shrink
                    # strategy BEFORE recover() shrinks them away
                    new_ctx = None
                    if rebuild is not None and regrows < max_regrows:
                        new_ctx = _elastic.make_regrow_context(
                            model, sig,
                            getattr(self.config, "regrow_probes", 2),
                            prior=regrow_ctx)
                    model, carry, kept = _elastic.recover(
                        model, sig, rebuild, olog=olog, log=log)
                    regrow_ctx = new_ctx
                    prior_losses = prior_losses + kept
                    resizes += 1
                    resize_dirs["shrink"] += 1
                except _elastic.DeviceReturnDetected as sig:
                    import jax as _jax

                    try:
                        # sync-ok: device-return recovery boundary — the
                        # old mesh's losses must land before the regrid
                        kept = [float(v) for v in
                                _jax.device_get(list(sig.losses))]
                    except Exception:
                        kept = []
                    try:
                        model, carry, _ = _elastic.recover_grow(
                            model, sig, regrow_ctx, rebuild,
                            olog=olog, log=log)
                    except Exception as e:
                        # growing is an optimization: never kill a
                        # healthy shrunk run over a failed expansion
                        olog.event("elastic_fallback", step=sig.step,
                                   reason=f"regrow failed: {e}")
                        log(f"elastic: regrow failed ({e}); continuing "
                            f"on {model.machine.num_devices} devices")
                        carry = {"start_iter": sig.step,
                                 "params": sig.params,
                                 "state": sig.state,
                                 "opt_state": sig.opt_state}
                    else:
                        resizes += 1
                        resize_dirs["grow"] += 1
                    regrow_ctx = None
                    regrows += 1
                    prior_losses = prior_losses + kept
        except BaseException:
            # error exit must release the multi-host coordinator promptly
            # — a crashed host previously held the barrier until the
            # other hosts' timeout (no-op unless THIS process initialized
            # jax.distributed)
            from flexflow_tpu import distributed

            distributed.release()
            raise
        finally:
            restore_sig()
            if restore_inj is not None:
                restore_inj()
            olog.close()

    def _fit(self, data_iter, num_iterations, warmup, log, olog, inj,
             elastic_resume=None, elastic_resizes=0, elastic_regrow=None,
             resize_dirs=None, drain=None):
        import contextlib

        import jax

        from flexflow_tpu.utils import checkpoint as ckpt
        from flexflow_tpu.utils import elastic as _elastic
        from flexflow_tpu.utils.health import StepHealthGuard, StepWatchdog

        if getattr(self.config, "dry_compile", False):
            # DISABLE_COMPUTATION analog (ops.h:19): run the whole graph/
            # partition/compile machinery — tracing, sharding propagation,
            # SPMD partitioning, XLA compilation — but materialize and
            # execute nothing (the train state enters lowering as avals).
            from flexflow_tpu.utils.profiling import normalize_cost_analysis

            t0 = time.perf_counter()
            compiled = self.compile_train_step(*next(data_iter))
            cost = normalize_cost_analysis(compiled)
            mem = compiled.memory_analysis()
            olog.event("compile", seconds=time.perf_counter() - t0,
                       flops=float(cost.get("flops", 0.0)),
                       bytes_accessed=float(cost.get("bytes accessed",
                                                     0.0)),
                       dry=True)
            log(f"dry-compile ok: {len(self.layers)} layers, "
                f"flops/step = {cost.get('flops', 0.0):.3e}, "
                f"argument bytes = "
                f"{getattr(mem, 'argument_size_in_bytes', 0)}")
            return {"params": None, "state": None, "loss": [],
                    "elapsed_s": 0.0, "images_per_sec": 0.0,
                    "compiled": compiled}

        # checkpoint/resume (TPU-native addition; the reference can only
        # serialize the strategy, strategy.cc:62-86 — see utils/checkpoint)
        start_iter = 0
        resumed = False
        ckpt_dir = getattr(self.config, "ckpt_dir", "")
        ckpt_freq = getattr(self.config, "ckpt_freq", 0)
        if elastic_resume is not None:
            # continuation after an elastic resize (utils/elastic.py):
            # state arrives already placed on THIS model's surviving
            # mesh; the data stream is NOT rewound — like rollback, the
            # resumed steps consume fresh batches
            start_iter = int(elastic_resume["start_iter"])
            params = elastic_resume["params"]
            state = elastic_resume["state"]
            opt_state = elastic_resume["opt_state"] \
                or self.init_opt_state(params)
            resumed = True
        elif ckpt_dir:
            if ckpt.latest_step(ckpt_dir) is not None:
                t0 = time.perf_counter()
                # verified restore with latest -> older fallback cascade
                # (utils/checkpoint.py); a corrupt latest step costs one
                # checkpoint interval, not the run
                start_iter, params, state, opt_state = \
                    ckpt.restore_checkpoint(ckpt_dir, self, olog=olog)
                olog.event("checkpoint_restore", step=start_iter,
                           seconds=time.perf_counter() - t0, dir=ckpt_dir)
                resumed = True
                opt_state = opt_state or self.init_opt_state(params)
                saved = ckpt.load_strategy(ckpt_dir, step=start_iter)
                if saved is not None \
                        and dict(saved) != dict(self.config.strategies):
                    log("warning: checkpoint was trained under a different "
                        "strategy; continuing under the current one")
                log(f"resumed from {ckpt_dir} at iteration {start_iter}")
                # re-align a deterministic (seeded) data stream with the
                # restored position so resume matches the uninterrupted run
                skip = min(start_iter, num_iterations)
                try:
                    for _ in range(skip):
                        next(data_iter)
                except StopIteration:
                    raise RuntimeError(
                        f"checkpoint at step {start_iter} is ahead of the "
                        f"data stream: the stream ended before yielding "
                        f"the {skip} batches needed to re-align resume — "
                        f"regenerate the stream, or point ckpt_dir at a "
                        f"checkpoint matching this data") from None
        if not resumed:
            params, state = self.init()
            opt_state = self.init_opt_state(params)
        # async checkpointing (utils/checkpoint.AsyncCheckpointWriter):
        # serialization + digest + fsync'd commit move to a background
        # writer; only the host snapshot stays on the boundary.  fit
        # blocks on it only at the final save and before a rollback
        # restore.  Off by default (--ckpt-async) — the sync path below
        # is unchanged.
        awriter = None
        if ckpt_dir and getattr(self.config, "ckpt_async", False):
            awriter = ckpt.AsyncCheckpointWriter(olog=olog, log=log)
        # elastic device-loss bookkeeping (utils/elastic.py): injected
        # ``device_loss`` fires mark ordinals dead here; detection is
        # deferred to the next host-sync boundary (zero new syncs), where
        # _raise_device_loss turns them into recovery or a fatal error
        elastic_dead: List[int] = []
        # transient-retry budget with a windowed refill: the budget (3)
        # only refills after transient_reset_steps CONSECUTIVE healthy
        # steps, so a long run absorbs spread-out hiccups while rapid
        # fail/succeed flapping still exhausts the cap
        transient_retries = 0
        healthy_streak = 0
        transient_reset = max(int(getattr(self.config,
                                          "transient_reset_steps", 16)
                                  or 0), 0)
        # step watchdog (utils/health.StepWatchdog): hang detection armed
        # around the boundary's blocking syncs; off unless --hang-factor
        # > 0, so healthy default runs carry no timer threads
        wd = None
        _hf = float(getattr(self.config, "hang_factor", 0.0) or 0.0)
        if _hf > 0:
            wd = StepWatchdog(
                _hf,
                min_deadline_s=float(getattr(self.config, "hang_min_s",
                                             60.0) or 60.0),
                olog=olog, log=log)
        hang_pending = False
        # double-buffered device prefetch (data/prefetch.py): host batch
        # prep + sharded H2D of step N+1 overlap step N's compute instead
        # of running synchronously inside the timed loop.  Wrapped AFTER
        # the resume skip so a deterministic stream stays aligned;
        # prefetch_depth=0 disables (the legacy synchronous pull).
        prefetcher = None
        _depth = max(int(getattr(self.config, "prefetch_depth", 2) or 0), 0)
        if _depth:
            from flexflow_tpu.data.prefetch import DevicePrefetcher

            prefetcher = DevicePrefetcher(data_iter, machine=self.machine,
                                          depth=_depth, olog=olog)
            data_iter = iter(prefetcher)
        step = self.make_train_step()
        warmup = start_iter + min(warmup,
                                  max(num_iterations - start_iter - 1, 0))
        # step health guard (utils/health.py): windowed finite-loss checks
        # at print/checkpoint boundaries only — the window's device losses
        # are already accumulated, so no per-step host sync is added and
        # a healthy run is byte-identical to an unguarded one
        guard = StepHealthGuard(
            policy=getattr(self.config, "on_divergence", "halt"),
            max_rollbacks=int(getattr(self.config, "max_rollbacks", 3)),
            olog=olog, log=log)

        trace_ctx = contextlib.nullcontext()
        if getattr(self.config, "trace_dir", ""):
            from flexflow_tpu.utils.profiling import trace

            trace_ctx = trace(self.config.trace_dir)

        # losses accumulate as raw device arrays — converted to floats in
        # ONE bulk transfer after the timed loop (no per-step sync, and
        # callers get plain numbers instead of pinned device buffers)
        losses = []
        # always-on live metrics (obs/metrics.py): gauges atomically
        # rewritten at the SAME host-sync boundaries the guard rides —
        # no new syncs, and independent of the obs JSONL being enabled
        from flexflow_tpu.obs import metrics as obs_metrics

        metrics = obs_metrics.from_config(
            self.config, meta={"model": type(self).__name__,
                               "run": olog.run_id or ""})
        # step-budget accounting (obs/budget.py): host time this run
        # spends on sync boundaries and checkpoint I/O, amortized into
        # the post-loop step_budget record.  Timing existing code only.
        host_sync_s = 0.0
        ckpt_io_s = 0.0
        fault_count = 0
        # obs: host-side per-step wall clock only — tick() never syncs,
        # and the per-step records are written AFTER the timed loop, so
        # the device pipeline is unperturbed.  Disabled: clock is None
        # and the loop pays one predicate check.
        clock = None
        if olog.enabled or metrics is not None:
            from flexflow_tpu.utils.profiling import StepClock

            clock = StepClock()
        # sampled per-op timing mode (obs/trace.py's measured side): every
        # Nth step drains the pipeline and times forward / fwd+bwd /
        # the real step, each host-synced, under jax.profiler
        # annotations.  Off by default — sampling perturbs the device
        # pipeline on sampled steps, so it is an explicit opt-in.
        sample_every = max(int(getattr(self.config, "op_time_every", 0)
                               or 0), 0) if olog.enabled else 0
        sections = self._make_section_fns() if sample_every else None
        op_samples = []
        start = time.perf_counter()
        loss = None
        # loss_base: absolute step of losses[0] (rollback may restore to
        # a step older than the resume point); window_start: first step
        # of the guard's current loss window
        loss_base = start_iter
        window_start = start_iter
        # watchdog estimate feed + graceful-drain outcome
        last_boundary_t = start
        last_boundary_it = start_iter
        drained_info = None
        try:
            with trace_ctx:
                it = start_iter
                while it < num_iterations:
                    batch = next(data_iter)
                    if it == warmup:
                        if loss is not None:
                            with obs.span("ff:runtime.fit_sync", step=it,
                                          what="warmup"):
                                # sync-ok: one-time warmup fence before
                                # the timed window opens
                                jax.block_until_ready(loss)
                        start = time.perf_counter()
                    try:
                        if sample_every and (it + 1) % sample_every == 0:
                            params, state, opt_state, loss = \
                                self._sampled_step(
                                    step, sections, op_samples, it,
                                    loss, params, state, opt_state,
                                    batch)
                        else:
                            params, state, opt_state, loss = step(
                                params, state, opt_state, *batch)
                        if transient_retries:
                            healthy_streak += 1
                            if transient_reset \
                                    and healthy_streak >= transient_reset:
                                transient_retries = 0
                                healthy_streak = 0
                                olog.event("recovery", source="elastic",
                                           after="transient_window",
                                           step=it + 1)
                    except Exception as e:
                        # device-loss classification (utils/elastic.py):
                        # a runtime error that probes TRANSIENT retries
                        # this iteration on a fresh batch; PERMANENT loss
                        # raises DeviceLossDetected (donated inputs are
                        # unreachable -> checkpoint-fallback recovery)
                        outcome = self._classify_step_error(
                            e, it + 1, olog, losses, loss_base,
                            transient_retries)
                        if outcome != "transient":
                            raise
                        transient_retries += 1
                        healthy_streak = 0
                        continue
                    if inj.enabled and inj.fire("loss_nan", site="fit"):
                        # poison the RECORDED loss device-side (no host
                        # sync); the guard detects it at the next boundary
                        loss = loss * float("nan")
                    if inj.enabled and inj.fire("host_crash", site="fit"):
                        from flexflow_tpu.utils.elastic import \
                            HostCrashError

                        raise HostCrashError(
                            f"injected host crash at iteration {it + 1}")
                    if inj.enabled and inj.fire("device_loss", site="fit"):
                        # mark the highest live ordinal PERMANENTLY dead;
                        # detection waits for the next host-sync boundary
                        alive = [i for i in
                                 range(self.machine.num_devices)
                                 if i not in elastic_dead]
                        if alive:
                            elastic_dead.append(alive[-1])
                    if inj.enabled and inj.fire("preempt", site="fit") \
                            and drain is not None:
                        # raise the REAL signal path (graceful drain)
                        _elastic.request_drain(drain)
                    if inj.enabled and inj.fire("step_hang", site="fit"):
                        # wedge the NEXT boundary past the watchdog
                        # deadline (utils/health.StepWatchdog.stall)
                        hang_pending = True
                    losses.append(loss)
                    if clock is not None:
                        clock.tick()
                    it1 = it + 1
                    at_print = bool(self.config.print_freq) \
                        and it1 % self.config.print_freq == 0
                    at_ckpt = bool(ckpt_dir) and bool(ckpt_freq) \
                        and it1 % ckpt_freq == 0 and it1 < num_iterations
                    at_boundary = at_print or at_ckpt \
                        or it1 == num_iterations
                    if at_boundary:
                        # guard check rides boundaries that host-sync
                        # anyway (print's float(loss), the save's
                        # device_get); the boundary's own host time feeds
                        # the step_budget host_sync bucket
                        if wd is not None:
                            # watchdog armed around the boundary's
                            # blocking syncs; the rolling estimate feeds
                            # on the inter-boundary wall clock
                            _now = time.perf_counter()
                            wd.observe(_now - last_boundary_t,
                                       it1 - last_boundary_it)
                            last_boundary_t = _now
                            last_boundary_it = it1
                            wd.arm(it1)
                            if hang_pending:
                                # injected wedge: block past the deadline
                                hang_pending = False
                                wd.stall()
                        if elastic_dead:
                            # injected permanent loss: hand the live loop
                            # state to the elastic wrapper for recovery
                            self._raise_device_loss(
                                elastic_dead, it1, params, state,
                                opt_state, losses, loss_base)
                        with obs.span("ff:runtime.fit_sync", step=it1,
                                      what="guard") as sp:
                            action = guard.check(
                                losses[window_start - loss_base:],
                                first_step=window_start + 1)
                        host_sync_s += sp.seconds
                        if action == "rollback":
                            if wd is not None:
                                wd.disarm()
                            if awriter is not None:
                                # the restore must see the newest commit
                                awriter.wait()
                            rstep, params, state, opt_state = \
                                self._rollback_restore(ckpt_dir, olog,
                                                       log, it1)
                            del losses[max(rstep - loss_base, 0):]
                            loss_base = min(loss_base, rstep)
                            loss = None
                            window_start = rstep
                            # the data stream is NOT rewound: steps re-run
                            # on fresh batches, past the bad window
                            it = rstep
                            continue
                        window_start = it1
                    if at_print:
                        with obs.span("ff:runtime.fit_sync", step=it1,
                                      what="print") as sp:
                            # sync-ok: print_freq-gated loss fetch,
                            # charged to host_sync_s in the step budget
                            log(f"iter {it1}: loss = {float(loss):.4f}")
                            self._publish_state_counters(state)
                        host_sync_s += sp.seconds
                    if at_ckpt:
                        t0 = time.perf_counter()
                        if awriter is not None:
                            # async: only the host snapshot + enqueue stay
                            # on the boundary; serialization/digest/commit
                            # run on the background writer
                            awriter.submit(ckpt_dir, it1, params, state,
                                           opt_state,
                                           self.config.strategies)
                            ckpt_io_s += time.perf_counter() - t0
                        else:
                            try:
                                ckpt.save_checkpoint(
                                    ckpt_dir, it1, params, state,
                                    opt_state, self.config.strategies)
                                dt = time.perf_counter() - t0
                                ckpt_io_s += dt
                                olog.event("checkpoint_save", step=it1,
                                           seconds=dt, dir=ckpt_dir)
                            except ckpt.NonFiniteCheckpointError as e:
                                # never commit non-finite state over good
                                # checkpoints; the guard decides the
                                # run's fate
                                fault_count += 1
                                ckpt_io_s += time.perf_counter() - t0
                                olog.event("fault", source="checkpoint",
                                           fault="nonfinite_state",
                                           step=it1, error=str(e))
                                log(f"warning: skipped checkpoint at "
                                    f"iteration {it1}: {e}")
                    if wd is not None and at_boundary:
                        # the boundary's blocking syncs are done; route a
                        # deadline expiry into the probe/classify path
                        # (transient -> keep training, permanent ->
                        # DeviceLossDetected -> shrink)
                        _hang = wd.disarm()
                        if _hang is not None:
                            self._handle_step_hang(
                                _hang, it1, params, state, opt_state,
                                losses, loss_base, olog, log)
                    if elastic_regrow and at_boundary \
                            and it1 < num_iterations \
                            and _elastic.probe_regrow(
                                elastic_regrow, inj=inj, olog=olog,
                                log=log):
                        # K consecutive healthy probes: hand the live
                        # state to the elastic wrapper for re-expansion
                        raise _elastic.DeviceReturnDetected(
                            [_elastic._device_ordinal(d)
                             for d, _ in elastic_regrow["dead"]],
                            it1, params=params, state=state,
                            opt_state=opt_state, losses=losses,
                            loss_base=loss_base)
                    if metrics is not None and (at_print or at_ckpt):
                        # refresh the scrape at a boundary that just
                        # synced
                        self._metrics_update(
                            metrics, olog, step, params, state, opt_state,
                            batch, losses, it1, warmup, start, guard,
                            prefetcher, fault_count, awriter=awriter,
                            elastic_resizes=elastic_resizes,
                            resize_dirs=resize_dirs,
                            draining=bool(drain
                                          and drain.get("requested")))
                    if drain is not None and drain.get("requested") \
                            and at_boundary and it1 < num_iterations:
                        # graceful drain: the in-flight step finished;
                        # commit a final verified checkpoint within the
                        # wall budget, record it, and leave cleanly
                        drained_info = self._drain_checkpoint(
                            ckpt_dir, awriter, it1, start_iter, params,
                            state, opt_state, drain, olog, log,
                            just_saved=at_ckpt)
                        it += 1
                        break
                    it += 1
                if loss is not None:
                    with obs.span("ff:runtime.fit_sync", step=it,
                                  what="close"):
                        # sync-ok: closes the timed window
                        jax.block_until_ready(loss)
                        self._publish_state_counters(state)
                elapsed = time.perf_counter() - start
        except BaseException:
            # error exit (host crash, device loss handed to the elastic
            # wrapper, genuine bug): stop the staging thread NOW — an
            # elastic continuation re-wraps the same upstream iterator,
            # and two live workers would interleave pulls — and abandon
            # the async writer without blocking on its queue
            if prefetcher is not None:
                prefetcher.close()
            if awriter is not None:
                awriter.close(timeout=5.0)
            if wd is not None:
                wd.close()
            raise
        if prefetcher is not None:
            # stop the staging thread before post-loop work; an
            # exceptional exit closes it via DevicePrefetcher.__del__
            prefetcher.close()
        if wd is not None:
            # cancel + join any armed timer so no watchdog thread
            # outlives the fit (the thread-leak checks assert this)
            wd.close()
        if ckpt_dir and start_iter < num_iterations \
                and drained_info is None:
            t0 = time.perf_counter()
            if awriter is not None:
                # the final save is the one write fit() blocks on: a
                # returning run must leave a committed, verified state
                awriter.submit(ckpt_dir, num_iterations, params, state,
                               opt_state, self.config.strategies)
                awriter.wait()
            else:
                try:
                    ckpt.save_checkpoint(ckpt_dir, num_iterations, params,
                                         state, opt_state,
                                         self.config.strategies)
                    olog.event("checkpoint_save", step=num_iterations,
                               seconds=time.perf_counter() - t0,
                               dir=ckpt_dir)
                except ckpt.NonFiniteCheckpointError as e:
                    olog.event("fault", source="checkpoint",
                               fault="nonfinite_state",
                               step=num_iterations, error=str(e))
                    log(f"warning: skipped final checkpoint: {e}")
        if awriter is not None:
            awriter.close()
        # the one bulk device->host transfer of the whole loss history.
        # end_step: last completed iteration (num_iterations normally;
        # the drained step after a graceful drain)
        end_step = it
        # sync-ok: end-of-run loss materialization, outside the loop
        losses = [float(l) for l in jax.device_get(losses)]
        n_timed = end_step - warmup
        throughput = (n_timed * self.config.batch_size / elapsed
                      if elapsed > 0 and n_timed > 0 else 0.0)
        log(f"time = {elapsed:.4f}s, tp = {throughput:.2f} images/s")
        if metrics is not None:
            # final scrape with the settled end-of-run numbers (also the
            # ONLY write for runs whose print/ckpt frequency never fired)
            self._metrics_update(metrics, olog, step, params, state,
                                 opt_state, batch if losses else None,
                                 losses, end_step, warmup, start,
                                 guard, prefetcher, fault_count,
                                 elapsed=elapsed, throughput=throughput,
                                 awriter=awriter,
                                 elastic_resizes=elastic_resizes,
                                 resize_dirs=resize_dirs,
                                 draining=drained_info is not None)
        if olog.enabled:
            budget_totals = {
                "host_sync_s": host_sync_s, "checkpoint_s": ckpt_io_s,
                "input_stall_s": prefetcher.stall_s if prefetcher else 0.0,
                "input_batches": prefetcher.batches if prefetcher else 0,
                "steps": end_step - start_iter,
            }
            self._emit_fit_records(olog, clock, losses, start_iter, warmup,
                                   end_step, elapsed, throughput,
                                   step, params, state, opt_state,
                                   batch if losses else None, op_samples,
                                   sample_every, budget_totals)
            # execution-performance records (round 6): the regrid plan's
            # coalescing accounting and the prefetch stall residual —
            # both strictly post-loop, like every other fit record
            rsum = self.regrid_plan_summary()
            if rsum:
                olog.event("regrid_plan", **rsum)
            if prefetcher is not None:
                olog.event("prefetch", **prefetcher.summary())
            olog.spans()
        if self.config.profiling:
            # Flag-gated profiling report (reference: per-task cudaEvent ms
            # when `profiling` is set, conv_2d.cu:514-545).  Lead with the
            # HONEST number — the compiled whole-step roofline (post-fusion
            # FLOPs over measured step time); the per-op isolated table
            # below it is an attribution guide, not a decomposition (XLA
            # fuses across ops — VERDICT r1 weak #6).
            from flexflow_tpu.sim.cost_model import run_perf
            from flexflow_tpu.utils.profiling import (OpProfiler,
                                                      compiled_roofline)

            if n_timed > 0 and elapsed > 0:
                # utilization is a fraction of the peaks of the chip the
                # loop ran on (an unknown TPU kind raises); off the chip
                # there is no MXU to be a fraction of
                dev = self.machine.devices[0]
                perf = run_perf(dev)
                rl = compiled_roofline(
                    step.lower(params, state, opt_state, *batch).compile(),
                    elapsed / n_timed, perf,
                    n_devices=self.machine.num_devices)
                mxu = (f", MXU {100.0 * rl['mxu_utilization']:.1f}% of "
                       f"{dev.device_kind} peak") if perf is not None else ""
                log(f"step roofline (compiled program): "
                    f"{rl['flops']:.3e} FLOPs/step, "
                    f"{rl['achieved_tflops']:.2f} TFLOP/s, "
                    f"{rl['achieved_hbm_gbps']:.1f} HBM GB/s{mxu}")
            log(OpProfiler(self).report())
        from flexflow_tpu.utils.chip import device_account

        out = {
            "params": params, "state": state,
            "loss": losses,
            # per device: resident train-state bytes + runtime memory
            "devices_held": device_account(self.machine.devices, params,
                                           state, opt_state),
            "elapsed_s": elapsed, "images_per_sec": throughput,
            "input_stall_s": prefetcher.stall_s if prefetcher else 0.0,
            "rollbacks": guard.rollbacks,
            "ckpt_async_saves": awriter.saves if awriter is not None
            else 0,
            "run_id": olog.run_id, "obs_path": olog.path,
            "metrics_path": metrics.path if metrics is not None else "",
            "completed_steps": end_step,
        }
        if drained_info is not None:
            out["drained"] = True
            out["drain"] = drained_info
        return out

    def _raise_device_loss(self, dead, step, params, state, opt_state,
                           losses, loss_base):
        """Turn accumulated injected device losses into the elastic
        wrapper's recovery signal (``--elastic``) or a fatal
        :class:`~flexflow_tpu.utils.elastic.DeviceLostError`."""
        from flexflow_tpu.utils import elastic

        if getattr(self.config, "elastic", False):
            raise elastic.DeviceLossDetected(
                dead=dead, step=step, params=params, state=state,
                opt_state=opt_state, losses=losses, loss_base=loss_base,
                injected=True)
        raise elastic.DeviceLostError(
            f"permanent device loss at iteration {step} (ordinals "
            f"{sorted(set(dead))}); run with --elastic to recover on "
            f"the surviving mesh")

    def _handle_step_hang(self, info, step, params, state, opt_state,
                          losses, loss_base, olog, log):
        """Route a step-watchdog expiry (utils/health.StepWatchdog) into
        the elastic probe/classify path once the wedged boundary finally
        returned: dead probes raise :class:`DeviceLossDetected` into the
        shrink recovery, healthy probes mean the hang was transient and
        training continues."""
        from flexflow_tpu.utils import elastic

        if not getattr(self.config, "elastic", False):
            raise elastic.DeviceLostError(
                f"boundary at iteration {step} exceeded the step "
                f"watchdog deadline ({info['deadline_s']:.1f}s); run "
                f"with --elastic to probe and recover instead of "
                f"failing")
        live, dead, transient = elastic.probe_devices(self.machine,
                                                      olog=olog)
        if dead:
            raise elastic.DeviceLossDetected(
                dead=dead, step=step, params=params, state=state,
                opt_state=opt_state, losses=losses, loss_base=loss_base)
        olog.event("device_loss", step=step, classification="transient",
                   transient=transient, source="watchdog",
                   deadline_s=info["deadline_s"])
        log(f"watchdog: iteration {step} boundary returned past its "
            f"{info['deadline_s']:.1f}s deadline but every device "
            f"probes healthy — continuing")

    def _drain_checkpoint(self, ckpt_dir, awriter, step, start_iter,
                          params, state, opt_state, drain, olog, log,
                          just_saved=False):
        """Commit the graceful-drain checkpoint within the
        ``--drain-budget-s`` wall budget (async writer wait with a
        best-effort sync-save fallback), emit the single
        ``preempt_drain`` record, and release the multi-host
        coordinator.  Returns the record dict (the ``drain`` entry of
        fit()'s result)."""
        from flexflow_tpu import distributed
        from flexflow_tpu.utils import checkpoint as ckpt

        t0 = time.perf_counter()
        budget = float(getattr(self.config, "drain_budget_s", 60.0)
                       or 60.0)
        mode = "none"
        ckpt_step = None
        if ckpt_dir:
            if awriter is not None:
                if not just_saved:
                    awriter.submit(ckpt_dir, step, params, state,
                                   opt_state, self.config.strategies)
                left = max(budget - (time.perf_counter() - t0), 0.05)
                if awriter.wait(timeout=left):
                    mode, ckpt_step = "async", step
                else:
                    log(f"drain: async writer missed the {budget:.0f}s "
                        f"budget; falling back to a best-effort sync "
                        f"save")
                    try:
                        ckpt.save_checkpoint(ckpt_dir, step, params,
                                             state, opt_state,
                                             self.config.strategies)
                        mode, ckpt_step = "sync_fallback", step
                    except Exception as e:
                        log(f"warning: drain checkpoint failed: {e}")
                        mode = "failed"
            elif just_saved:
                # this boundary's synchronous save already committed
                mode, ckpt_step = "boundary_save", step
            else:
                try:
                    ckpt.save_checkpoint(ckpt_dir, step, params, state,
                                         opt_state,
                                         self.config.strategies)
                    olog.event("checkpoint_save", step=step,
                               seconds=time.perf_counter() - t0,
                               dir=ckpt_dir)
                    mode, ckpt_step = "sync", step
                except Exception as e:
                    log(f"warning: drain checkpoint failed: {e}")
                    mode = "failed"
        seconds = time.perf_counter() - t0
        info = {"step": step, "steps_completed": step,
                "ckpt_step": ckpt_step, "signal": drain.get("signum"),
                "seconds": seconds, "budget_s": budget, "mode": mode}
        olog.event("preempt_drain", **info)
        at = (f"checkpoint at step {ckpt_step}" if ckpt_step is not None
              else "no checkpoint")
        log(f"drain: stopped cleanly at iteration {step} ({at}, "
            f"{seconds:.2f}s of the {budget:.0f}s budget, mode {mode})")
        # a draining host must release its coordinator slot promptly —
        # idempotent with the error path's release
        distributed.release()
        return info

    def _classify_step_error(self, e, step, olog, losses, loss_base,
                             transient_retries):
        """Elastic classification of a step-execution error: returns
        ``"transient"`` when the device probe recovers (caller retries
        the iteration on a fresh batch, bounded at 3 consecutive
        retries), raises :class:`DeviceLossDetected` on permanent loss
        (with ``params=None`` — the failed step's donated inputs are
        unreachable, so recovery restores from checkpoint), and returns
        None for anything that is not device loss (caller re-raises)."""
        if not getattr(self.config, "elastic", False):
            return None
        from flexflow_tpu.utils import elastic

        if not elastic.classify(e):
            return None
        live, dead, transient = elastic.probe_devices(self.machine,
                                                      olog=olog)
        if dead:
            raise elastic.DeviceLossDetected(
                dead=dead, step=step, params=None, state=None,
                opt_state=None, losses=losses,
                loss_base=loss_base) from e
        if transient_retries >= 3:
            return None  # persistent failure with healthy probes: a bug
        olog.event("device_loss", step=step, classification="transient",
                   transient=transient, error=str(e))
        return "transient"

    def _rollback_restore(self, ckpt_dir, olog, log, from_step):
        """The health guard's rollback: restore the last VERIFIED
        checkpoint (cascading past corrupt steps) and return
        ``(step, params, state, opt_state)``.  Without a usable
        checkpoint the run restarts from a fresh init at step 0.  The
        data stream is never rewound — re-run steps consume fresh
        batches, which is what lets a one-off bad window be skipped."""
        from flexflow_tpu.utils import checkpoint as ckpt

        rstep, params, state, opt_state = 0, None, None, None
        if ckpt_dir:
            try:
                rstep, params, state, opt_state = \
                    ckpt.restore_checkpoint(ckpt_dir, self, olog=olog)
            except (FileNotFoundError, ckpt.CheckpointError) as e:
                log(f"rollback: no usable checkpoint under {ckpt_dir!r} "
                    f"({e}); reinitializing from step 0")
        if params is None:
            rstep = 0
            params, state = self.init()
            opt_state = None
        opt_state = opt_state or self.init_opt_state(params)
        olog.event("rollback", from_step=from_step, to_step=rstep,
                   dir=ckpt_dir or None)
        log(f"health guard: rolled back from iteration {from_step} to "
            f"checkpoint step {rstep}")
        return rstep, params, state, opt_state

    def _make_section_fns(self):
        """Jitted forward and forward+backward sections of the train step
        (the op-timing mode's section timers).  Pure — no donation, no
        state/opt mutation — so a sampled step can time them against the
        live params without advancing training."""
        import jax
        import jax.numpy as jnp

        cdtype = self.config.compute_dtype

        def cast(batch):
            return [b.astype(cdtype)
                    if hasattr(b, "dtype")
                    and jnp.issubdtype(b.dtype, jnp.floating) else b
                    for b in batch]

        def fwd(params, state, *batch):
            loss, _ = self.loss_fn(params, state, *cast(batch),
                                   train=True)
            return loss

        def fwd_bwd(params, state, *batch):
            def lf(p):
                loss, _ = self.loss_fn(p, state, *cast(batch), train=True)
                return loss

            return jax.value_and_grad(lf)(params)

        return jax.jit(fwd), jax.jit(fwd_bwd)

    def _sampled_step(self, step, sections, op_samples, it, prev_loss,
                      params, state, opt_state, batch):
        """One step of the sampled op-timing mode: drain the async
        pipeline, time the forward and forward+backward sections, then
        run the REAL training step host-synced — backward and optimizer
        times fall out by subtraction.  ``ff:profiling.*`` spans bracket
        each section so an XProf trace of the same run carries the
        boundaries.  Raw samples are buffered; op_time records are
        written after the timed loop."""
        import jax

        fwd, fwd_bwd = sections
        if prev_loss is not None:
            jax.block_until_ready(prev_loss)  # drain the pipeline
        rec = {"step": it + 1}
        t0 = time.perf_counter()
        with obs.span("ff:profiling.forward", step=it + 1):
            jax.block_until_ready(fwd(params, state, *batch))
        rec["forward"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        with obs.span("ff:profiling.forward_backward", step=it + 1):
            jax.block_until_ready(fwd_bwd(params, state, *batch))
        rec["forward_backward"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        with jax.profiler.StepTraceAnnotation("train", step_num=it + 1):
            out = step(params, state, opt_state, *batch)
        # loss, the step's dependency-chain tail
        jax.block_until_ready(out[3])
        rec["step_s"] = time.perf_counter() - t0
        op_samples.append(rec)
        return out

    def _emit_op_times(self, olog, op_samples):
        """The op_time records of one sampled run: per-sample section
        timings (backward/optimizer by subtraction, clamped at 0 — a
        sampled wall can jitter below its contained section) and one
        isolated per-op shard timing per layer under its executed config
        — the join keys drift attribution matches against the simulated
        per-op times."""
        for s in op_samples:
            fw = s.get("forward", 0.0)
            fb = s.get("forward_backward", 0.0)
            st = s.get("step_s", 0.0)
            for name, secs in (("forward", fw),
                               ("backward", max(fb - fw, 0.0)),
                               ("optimizer", max(st - fb, 0.0)),
                               ("step", st)):
                olog.event("op_time", scope="section", section=name,
                           step=s["step"], seconds=secs)
        from flexflow_tpu.sim.cost_model import AnalyticCostModel
        from flexflow_tpu.utils.profiling import time_op_shard

        analytic = AnalyticCostModel()
        rows = []
        for op in self.layers:
            t = time_op_shard(op, op.pc,
                              dtype=self.config.compute_dtype)
            measured = t is not None
            if not measured:  # unrealizable shard: analytic stand-in
                t = analytic.op_cost(op, op.pc)
            olog.event("op_time", scope="op", op=op.name,
                       op_kind=type(op).__name__, grid=list(op.pc.dims),
                       seconds=t, measured=measured)
            rows.append({"op": op.name, "seconds": float(t),
                         "measured": measured})
        return rows

    def _compiled_cost_stats(self, cache, step, params, state, opt_state,
                             batch):
        """Memoized compiled-step stats for the live gauges: post-fusion
        FLOPs / bytes (XLA cost analysis) and an HBM-footprint estimate
        from ``memory_analysis()`` (arguments + outputs − aliased +
        temporaries).  Lowering hits jit's trace/compile caches — one
        cheap call at the first boundary, then served from ``cache``."""
        if "cost" in cache:
            return cache["cost"]
        cost = {}
        if batch is not None:
            from flexflow_tpu.utils.profiling import \
                normalize_cost_analysis

            compiled = step.lower(params, state, opt_state,
                                  *batch).compile()
            ca = normalize_cost_analysis(compiled)
            cost["flops"] = float(ca.get("flops", 0.0))
            cost["bytes"] = float(ca.get("bytes accessed", 0.0))
            mem = compiled.memory_analysis()
            live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                    - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
            if live > 0:
                cost["hbm_est"] = float(live)
        cache["cost"] = cost
        return cost

    def _metrics_update(self, metrics, olog, step, params, state,
                        opt_state, batch, losses, it1, warmup, start_t,
                        guard, prefetcher, fault_count, elapsed=None,
                        throughput=None, awriter=None,
                        elastic_resizes=0, resize_dirs=None,
                        draining=False):
        """Refresh and publish the live gauges (obs/metrics.py) at a
        boundary that already host-synced.  Every input is host-resident
        or memoized; the one potentially non-trivial call (compiled cost
        analysis) runs once per fit and is served from the exporter's
        cache afterwards."""
        from flexflow_tpu.sim.cost_model import run_perf

        cost = self._compiled_cost_stats(metrics.cache, step, params,
                                         state, opt_state, batch)
        n_timed = it1 - warmup
        if elapsed is None:
            elapsed = time.perf_counter() - start_t
        if throughput is None:
            throughput = (n_timed * self.config.batch_size / elapsed
                          if n_timed > 0 and elapsed > 0 else None)
        step_s = (elapsed / n_timed if n_timed > 0 and elapsed > 0
                  else None)
        # mfu / mfu_ceiling are fractions of the peaks of the chip the
        # loop runs on (an unknown TPU kind raises); off the chip the two
        # gauges are not published
        perf = run_perf(self.machine.devices[0])
        mfu = mfu_ceiling = None
        flops = cost.get("flops")
        if perf is not None and flops and step_s:
            peak = perf.peak_flops * max(self.machine.num_devices, 1)
            hbm_bw = perf.hbm_bandwidth * max(self.machine.num_devices, 1)
            mfu = flops / step_s / peak
            floor = max(flops / peak, cost.get("bytes", 0.0) / hbm_bw)
            if floor > 0:
                mfu_ceiling = flops / floor / peak
        from flexflow_tpu.utils.chip import max_memory_stat

        hbm_live = max_memory_stat(self.machine.devices, "bytes_in_use")
        hbm_peak = max_memory_stat(self.machine.devices,
                                   "peak_bytes_in_use")
        if hbm_peak is None:
            hbm_peak = cost.get("hbm_est")
        last_loss = None
        if losses:
            try:  # boundary already synced; float() is a cheap copy
                last_loss = float(losses[-1])
            except (TypeError, ValueError):
                pass
        try:  # parameter residency at storage dtype (halves under bf16)
            param_bytes = float(sum(
                v.size * v.dtype.itemsize
                for sub in params.values() for v in sub.values()))
        except Exception:
            param_bytes = None
        metrics.update(
            param_bytes_total=param_bytes,
            throughput_items_per_sec=throughput,
            images_per_sec=throughput,
            mfu=mfu, mfu_ceiling=mfu_ceiling,
            step_wall_seconds=step_s, loss=last_loss,
            steps_total=it1,
            hbm_peak_bytes=hbm_peak, hbm_live_bytes=hbm_live,
            prefetch_stall_seconds_total=(prefetcher.stall_s
                                          if prefetcher else 0.0),
            rollbacks_total=guard.rollbacks,
            faults_total=fault_count + (awriter.faults
                                        if awriter is not None else 0),
            elastic_events=elastic_resizes,
            drain_pending=1.0 if draining else 0.0,
            ckpt_async_inflight=(awriter.inflight
                                 if awriter is not None else 0))
        for direction in ("shrink", "grow"):
            # per-direction labeled series alongside the plain total
            metrics.update_labeled(
                "elastic_events", {"direction": direction},
                (resize_dirs or {}).get(direction, 0))
        try:
            metrics.write()
        except OSError as e:
            import warnings

            warnings.warn(f"metrics export failed: {e}", RuntimeWarning)
            return
        # mirror the published snapshot into the obs stream so the
        # scrape and the JSONL never disagree (and the Perfetto counter
        # lanes have a source)
        olog.event("metrics", path=metrics.path,
                   **metrics.finite_values())

    def _sim_comm_s(self):
        """The simulator's collective-seconds estimate for the loaded
        strategy (per-op collective + dispatch overhead,
        StrategySearch.cost_breakdown) — the preferred source of the
        step_budget ``comm`` bucket.  None when no strategy is loaded or
        the simulation fails."""
        if not self.config.strategies:
            return None
        try:
            from flexflow_tpu.sim.search import StrategySearch

            ss = StrategySearch(self, machine=self.machine)
            rows = ss.cost_breakdown(
                ss.assignment_for(self.config.strategies))
            return sum(r["collective_s"] for r in rows)
        except Exception:
            return None

    def _emit_step_budget(self, olog, totals, op_samples, op_rows,
                          elapsed, n_timed):
        """The run's ``step_budget`` record (obs/budget.py): one sampled
        (or loop-mean) step's wall time decomposed into compute / comm /
        input_stall / host_sync / checkpoint / residual buckets, every
        input an existing measurement or an amortized total — zero new
        syncs.  Skipped only when the run produced no timed steps."""
        from flexflow_tpu.obs.budget import build_step_budget

        sources = {}
        walls = sorted(s["step_s"] for s in op_samples
                       if s.get("step_s"))
        if walls:
            wall = walls[len(walls) // 2]
            sources["wall"] = "sampled_step"
        elif n_timed > 0 and elapsed > 0:
            wall = elapsed / n_timed
            sources["wall"] = "loop_mean"
        else:
            return
        compute = None
        if op_rows:
            # isolated per-op shard timings estimate fwd+bwd compute
            # without collectives; the optimizer section (real step minus
            # fwd+bwd section) adds the update's compute + its comm
            iso = sum(r["seconds"] for r in op_rows)
            opts = sorted(max(s["step_s"] - s["forward_backward"], 0.0)
                          for s in op_samples
                          if s.get("step_s") is not None
                          and s.get("forward_backward") is not None)
            opt = opts[len(opts) // 2] if opts else 0.0
            compute = iso + opt
            sources["compute"] = (
                "isolated_ops+optimizer_section"
                if all(r["measured"] for r in op_rows)
                else "isolated_ops(analytic_standins)+optimizer_section")
        comm = self._sim_comm_s()
        if comm is not None:
            sources["comm"] = "sim"
        elif op_rows:
            # measured residual: the fused fwd+bwd section minus the
            # isolated compute sum is the in-step communication the
            # isolated harness cannot see (clamped — isolation overhead
            # can exceed fusion wins)
            fbs = sorted(s["forward_backward"] for s in op_samples
                         if s.get("forward_backward") is not None)
            if fbs:
                comm = max(fbs[len(fbs) // 2]
                           - sum(r["seconds"] for r in op_rows), 0.0)
                sources["comm"] = "section_residual"
        steps = max(int(totals.get("steps", 0)), 1)
        batches = int(totals.get("input_batches", 0)) or steps
        bud = build_step_budget(
            wall,
            compute_s=compute,
            comm_s=comm,
            input_stall_s=totals.get("input_stall_s", 0.0) / batches,
            host_sync_s=totals.get("host_sync_s", 0.0) / steps,
            checkpoint_s=totals.get("checkpoint_s", 0.0) / steps,
            sources=sources, n_samples=len(op_samples))
        olog.event("step_budget", **bud)

    def _emit_fit_records(self, olog, clock, losses, start_iter, warmup,
                          num_iterations, elapsed, throughput,
                          step, params, state, opt_state, batch,
                          op_samples=(), sample_every=0,
                          budget_totals=None):
        """Write the fit surface's obs records (compile, per-step, summary,
        op_time, sim_drift, step_budget).  Runs strictly AFTER the timed
        loop — the only in-loop obs costs are StepClock.tick() and, when
        the op-timing mode is on, the sampled steps' explicit syncs."""
        bsz = self.config.batch_size
        # one-time compile record: the first call's wall time is the
        # host-observable compile cost (trace + partition + XLA compile +
        # one step); post-fusion FLOPs/bytes come from the compiled
        # executable's cost analysis (lowering hits jit's trace cache)
        compile_rec = {"seconds": clock.deltas[0] if clock.deltas else 0.0}
        if batch is not None:
            from flexflow_tpu.utils.profiling import (
                normalize_cost_analysis, pallas_kernel_calls)

            compiled = step.lower(params, state, opt_state,
                                  *batch).compile()
            ca = normalize_cost_analysis(compiled)
            compile_rec["flops"] = float(ca.get("flops", 0.0))
            compile_rec["bytes_accessed"] = float(
                ca.get("bytes accessed", 0.0))
            # which Pallas kernels Mosaic compiled INTO this step (TPU
            # custom calls by kernel name) — routing is decided from the
            # backend at trace time, so the compiled program is the only
            # proof a kernel did not quietly stay on its XLA path
            compile_rec["pallas_kernels"] = pallas_kernel_calls(
                compiled.as_text())
        olog.event("compile", **compile_rec)
        for i, dt in enumerate(clock.deltas):
            it = start_iter + i
            olog.event("step", step=it + 1, wall_ms=dt * 1e3,
                       loss=losses[i] if i < len(losses) else None,
                       images_per_sec=bsz / dt if dt > 0 else 0.0,
                       timed=it >= warmup)
        olog.event("summary", iterations=num_iterations - start_iter,
                   warmup=warmup - start_iter, elapsed_s=elapsed,
                   images_per_sec=throughput,
                   final_loss=losses[-1] if losses else None)
        op_rows = []
        if sample_every and op_samples:
            op_rows = self._emit_op_times(olog, op_samples)
        if budget_totals is not None:
            self._emit_step_budget(olog, budget_totals, op_samples,
                                   op_rows, elapsed,
                                   num_iterations - warmup)
        # sim_drift, or an explicit record of WHY it is missing — a
        # silently absent gauge reads as "no drift" (round-1 satellite)
        n_timed = num_iterations - warmup
        if not self.config.strategies:
            olog.event("sim_drift_unavailable",
                       reason="no strategy loaded (pure-DP default run; "
                              "no simulator prediction to compare)")
        elif n_timed <= 0 or elapsed <= 0:
            olog.event("sim_drift_unavailable",
                       reason="no timed steps (every iteration was "
                              "warmup)")
        else:
            self._emit_sim_drift(olog, elapsed / n_timed)

    def _emit_sim_drift(self, olog, measured_step_s):
        """The simulator-calibration gauge: measured step time vs the
        simulator's prediction for the loaded strategy.  Prefers the
        prediction the search artifact carries (``__predicted__``, written
        by apps/search.py); falls back to simulating this model's
        strategy with the analytic cost model.  value = measured/predicted
        — >1 means the simulator is optimistic (the round-4
        transformer_2x4 falsification was this signal at ~8x on comm
        volume); drift-driven recalibration reads this record."""
        pred = getattr(self.config.strategies, "predicted", None)
        predicted_s, source = None, None
        if pred and pred.get("best_time_s"):
            predicted_s, source = float(pred["best_time_s"]), "artifact"
        else:
            try:
                from flexflow_tpu.sim.search import StrategySearch

                ss = StrategySearch(self, machine=self.machine)
                predicted_s = ss.simulate(
                    ss.assignment_for(self.config.strategies))
                source = "analytic"
            except Exception as e:
                olog.event("sim_drift_unavailable", error=str(e),
                           reason=f"simulating the loaded strategy "
                                  f"failed: {e}")
                return
        if predicted_s and predicted_s > 0:
            olog.event("sim_drift", name="sim_drift",
                       value=measured_step_s / predicted_s,
                       predicted_s=predicted_s,
                       measured_s=measured_step_s, source=source)
        else:
            olog.event("sim_drift_unavailable",
                       reason="artifact carries a non-positive "
                              "prediction")

    def summary(self) -> str:
        lines = [f"FFModel: {len(self.layers)} layers, "
                 f"{self.machine.num_devices} devices"]
        for op in self.layers:
            lines.append(
                f"  {op.name:<16s} {type(op).__name__:<10s} "
                f"grid={op.pc.dims} out={op.output.shape}")
        return "\n".join(lines)
