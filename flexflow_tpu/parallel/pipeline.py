"""Pipeline parallelism: an explicit GPipe-style microbatch scheduler.

The reference has NO pipeline scheduler — its "pipelining" is emergent:
per-(layer, chunk) ops placed on different GPUs execute as a wavefront
under Legion's async task graph (SURVEY.md §2.6 "PP de-facto",
nmt/rnn.cu:298-326).  This module supplies the explicit capability,
TPU-native:

  * stages live on a named mesh axis (``stage``); each stage holds its own
    slice of the stacked stage parameters (sharded over that axis);
  * microbatches stream through the ring: every tick each device applies
    its stage to its current activation, then ``ppermute`` rotates
    activations one stage forward over neighbor ICI links;
  * the schedule is GPipe (fill, steady state, drain): M microbatches over
    S stages take M + S - 1 ticks with an S-1 bubble; backward is jax
    autodiff through the scan + ppermute (the transpose of a shift is the
    reverse shift), which interleaves into the same ring;
  * composes with data parallelism: extra mesh axes (e.g. ``n``) shard the
    microbatch batch dim; replicated-param cotangents are reduced by
    shard_map's transpose machinery.

All collectives are neighbor ppermutes — no all-to-all, no host round
trips; exactly the layout "How to Scale Your Model" prescribes for
pipelining on TPU meshes.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def microbatch(x, num_microbatches: int):
    """(B, ...) -> (M, B//M, ...) leading microbatch axis."""
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(
            f"batch {b} not divisible by num_microbatches {num_microbatches}")
    return x.reshape((num_microbatches, b // num_microbatches) + x.shape[1:])


def spmd_pipeline(stage_fn: Callable, stage_params, xs, mesh: Mesh,
                  stage_axis: str = "stage",
                  batch_spec: Optional[P] = None,
                  param_specs=None):
    """Run microbatches through a homogeneous pipeline of S stages.

    stage_fn(params_one_stage, x_mb) -> y_mb; activations must keep the
    same shape through every stage (the classic pipeline contract).

    stage_params: pytree with a leading axis of size S (stage-stacked),
    sharded over ``stage_axis``.  xs: (M, mb, ...) microbatched input.
    batch_spec: PartitionSpec of one microbatch's data dims (after the
    leading M axis), e.g. P("n") to shard the microbatch over a data
    axis; defaults to fully replicated.
    param_specs: optional pytree (matching stage_params) of per-leaf
    PartitionSpecs — round 5: stage params may be TENSOR-PARALLEL within
    each stage's submesh (leaf dims sharded over e.g. a "tp" axis in
    addition to the leading stage axis); stage_fn then runs with those
    axes live and inserts its own psums.  Default: every leaf
    P(stage_axis) (stage-stacked, otherwise replicated).

    Returns (M, mb, ...) outputs, replicated over ``stage_axis``.
    """
    s_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    num_stages = s_sizes[stage_axis]
    num_mb = xs.shape[0]
    for leaf in jax.tree.leaves(stage_params):
        if leaf.shape[0] != num_stages:
            raise ValueError(
                f"stage_params leading dim {leaf.shape[0]} != stage mesh "
                f"axis size {num_stages}; each device must hold exactly "
                f"one stage slice")
    data_spec = batch_spec if batch_spec is not None else P()
    xs_spec = P(None, *data_spec)   # leading M axis never sharded
    param_spec = param_specs if param_specs is not None \
        else jax.tree.map(lambda _: P(stage_axis), stage_params)

    def pipelined(params, xs_local):
        local_params = jax.tree.map(lambda p: p[0], params)
        idx = lax.axis_index(stage_axis)
        ticks = num_mb + num_stages - 1
        zero = jnp.zeros(xs_local.shape[1:], xs_local.dtype)
        perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

        def tick(carry, t):
            recv = carry
            x_t = lax.dynamic_index_in_dim(
                xs_local, jnp.clip(t, 0, num_mb - 1), 0, keepdims=False)
            inp = jnp.where(idx == 0, x_t, recv)
            y = stage_fn(local_params, inp)
            recv_next = lax.ppermute(y, stage_axis, perm)
            return recv_next, y

        _, ys = lax.scan(tick, zero, jnp.arange(ticks))
        # stage S-1 emits microbatch m at tick m + S - 1
        out_local = lax.slice_in_dim(ys, num_stages - 1,
                                     num_stages - 1 + num_mb, axis=0)
        # broadcast the last stage's outputs to every stage (masked psum)
        out = lax.psum(
            jnp.where(idx == num_stages - 1, out_local,
                      jnp.zeros_like(out_local)),
            stage_axis)
        return out

    return jax.shard_map(
        pipelined, mesh=mesh,
        in_specs=(param_spec, xs_spec),
        out_specs=xs_spec,
        check_vma=False,
    )(stage_params, xs)


def sequential_reference(stage_fn: Callable, stage_params, xs):
    """Non-pipelined ground truth: apply the S stages in order to each
    microbatch (used by tests to pin the pipeline's semantics)."""
    num_stages = jax.tree.leaves(stage_params)[0].shape[0]

    def apply_all(x_mb):
        for s in range(num_stages):
            p_s = jax.tree.map(lambda p: p[s], stage_params)
            x_mb = stage_fn(p_s, x_mb)
        return x_mb

    return jax.vmap(apply_all)(xs)


# ----------------------------------------------------------------------
# pipelined transformer blocks (flagship integration)


def _layer_norm(g, b, x, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def transformer_block_fn(num_heads: int, causal: bool = False,
                         tp_axis: Optional[str] = None):
    """A pre-norm transformer block as a pipeline stage_fn.  Params:
    {"ln1": (2, D), "wqkv": (D, 3, D), "bqkv": (3, D), "wo": (D, D),
     "bo": (D,), "ln2": (2, D), "w1": (D, F), "b1": (F,), "w2": (F, D),
     "b2": (D,)}.

    Round 5 — stage-internal tensor parallelism: with ``tp_axis`` set
    (a live mesh axis inside the pipeline shard_map) the block is
    Megatron-sharded over it: wqkv/bqkv/w1/b1 column-split, wo/w2
    row-split (see :func:`stage_param_specs`), each device computes its
    head/ffn slice from the replicated activation, and the two partial
    products psum over the axis.  With tp_axis=None the same code runs
    the full block (the sequential reference path) — the local head
    count is derived from the actual shard shapes, so one body serves
    both."""

    def block(p, x):
        d = x.shape[-1]
        head_dim = d // num_heads
        h = _layer_norm(p["ln1"][0], p["ln1"][1], x)
        # (B, S, 3, E) where E = D/tp locally: q/k/v each get their own
        # contiguous head subset (the (D, 3, D) layout keeps the three
        # projections separable under a last-dim shard)
        qkv = jnp.einsum("bsd,dte->bste", h, p["wqkv"]) + p["bqkv"]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

        def heads(t):  # (B, S, E) -> (B, H_local, S, d_h)
            b_, s_, e_ = t.shape
            return t.reshape(b_, s_, e_ // head_dim, head_dim) \
                    .transpose(0, 2, 1, 3)

        q, k, v = heads(q), heads(k), heads(v)
        scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, x.dtype))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        if causal:
            mask = jnp.tril(jnp.ones(s.shape[-2:], bool))
            s = jnp.where(mask, s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", a, v)
        b_, hl, s_, _ = o.shape
        o = o.transpose(0, 2, 1, 3).reshape(b_, s_, hl * head_dim)
        attn = o @ p["wo"]            # (E, D) row-shard -> partial sums
        if tp_axis is not None:
            attn = lax.psum(attn, tp_axis)
        x = x + attn + p["bo"]

        h = _layer_norm(p["ln2"][0], p["ln2"][1], x)
        h = jax.nn.gelu(h @ p["w1"] + p["b1"])
        ffn = h @ p["w2"]             # (F/tp, D) row-shard -> partial
        if tp_axis is not None:
            ffn = lax.psum(ffn, tp_axis)
        return x + ffn + p["b2"]

    return block


def init_block_stack(rng, num_stages: int, d_model: int, d_ff: int):
    """Stage-stacked transformer block params (leading axis = stage).
    wqkv is (D, 3, D) — the three projections on their own dim, so a
    last-dim tensor-parallel shard splits each of q/k/v by heads instead
    of slicing across the q|k|v concatenation."""
    ks = jax.random.split(rng, 4)
    shapes = {
        "ln1": ((2, d_model), None),
        "wqkv": ((d_model, 3, d_model), 0),
        "bqkv": ((3, d_model), None),
        "wo": ((d_model, d_model), 1),
        "bo": ((d_model,), None),
        "ln2": ((2, d_model), None),
        "w1": ((d_model, d_ff), 2),
        "b1": ((d_ff,), None),
        "w2": ((d_ff, d_model), 3),
        "b2": ((d_model,), None),
    }
    params = {}
    for name, (shape, ki) in shapes.items():
        full = (num_stages,) + shape
        if ki is None:
            init = jnp.zeros(full, "float32")
            if name.startswith("ln"):
                init = init.at[:, 0].set(1.0)  # scale=1, bias=0
            params[name] = init
        else:
            fan_in = shape[0]
            params[name] = jax.random.normal(ks[ki], full, "float32") \
                * (1.0 / jnp.sqrt(fan_in))
    return params


def stage_param_specs(stage_axis: str = "stage",
                      tp_axis: Optional[str] = None,
                      sub_dims: int = 0):
    """Per-leaf PartitionSpecs of the block stack: stage-stacked on the
    leading axis, and (round 5) Megatron-sharded over ``tp_axis`` —
    wqkv/bqkv/w1/b1 column-split (head/ffn slices), wo/w2 row-split
    (partials psum in the block).  ``sub_dims`` extra None dims between
    the stage axis and the param dims (PipelinedLM stacks (S, L/S, ...))."""
    s = (stage_axis,) + (None,) * sub_dims
    t = tp_axis
    return {
        "ln1": P(*s), "ln2": P(*s), "bo": P(*s), "b2": P(*s),
        "wqkv": P(*s, None, None, t), "bqkv": P(*s, None, t),
        "wo": P(*s, t, None), "w1": P(*s, None, t),
        "b1": P(*s, t), "w2": P(*s, t, None),
    }


def place_stage_params(params, mesh: Mesh, stage_axis: str = "stage",
                       param_specs=None):
    """Shard the stage-stacked params over the stage axis of ``mesh``
    (and any additional per-leaf axes in ``param_specs``)."""
    if param_specs is None:
        return jax.tree.map(
            lambda p: jax.device_put(
                p, NamedSharding(mesh, P(*((stage_axis,) +
                                           (None,) * (p.ndim - 1))))),
            params)
    return jax.tree.map(
        lambda p, spec: jax.device_put(p, NamedSharding(mesh, spec)),
        params, param_specs)


# ----------------------------------------------------------------------
# PipelinedLM: a complete causal/encoder LM trained through the GPipe
# ring — the driver-level integration of pipeline parallelism
# (apps/lm --pipeline-stages), composing PP (stage axis) x DP (n axis).


class PipelinedLM:
    """Embed -> L transformer blocks split over S pipeline stages ->
    final-norm -> vocab head + CE.  Blocks run through spmd_pipeline on a
    ('stage', 'n') mesh; embed/head run under plain GSPMD batch sharding.

    Not an FFModel: stage params are stacked on a leading axis (one slice
    per device along 'stage'), which is a different parameter layout than
    the op DAG; the op-DAG path covers per-layer SOAP strategies, this
    class covers explicit microbatch pipelining of a homogeneous stack.
    """

    def __init__(self, machine, num_stages: int, num_microbatches: int,
                 num_layers: int = 12, d_model: int = 768,
                 num_heads: int = 12, d_ff: int = 3072,
                 vocab_size: int = 32768, seq_length: int = 512,
                 batch_size: int = 16, causal: bool = True,
                 learning_rate: float = 1e-3, compute_dtype="float32",
                 tp: int = 1):
        import numpy as np

        if num_layers % num_stages:
            raise ValueError(f"{num_layers} layers not divisible into "
                             f"{num_stages} stages")
        if machine.num_devices % (num_stages * tp):
            raise ValueError(f"{machine.num_devices} devices not divisible "
                             f"into {num_stages} stages x {tp} tp")
        if num_heads % tp or d_ff % tp:
            raise ValueError(f"tp={tp} must divide num_heads ({num_heads}) "
                             f"and d_ff ({d_ff})")
        if batch_size % num_microbatches:
            raise ValueError("batch not divisible by microbatches")
        dp = machine.num_devices // (num_stages * tp)
        if (batch_size // num_microbatches) % dp:
            raise ValueError(
                f"microbatch size {batch_size // num_microbatches} not "
                f"divisible by the data-parallel axis ({dp} devices)")
        self.machine = machine
        self.S, self.M, self.tp = num_stages, num_microbatches, tp
        self.L, self.D, self.H = num_layers, d_model, num_heads
        self.F, self.V = d_ff, vocab_size
        self.seq, self.batch = seq_length, batch_size
        self.causal = causal
        self.lr = learning_rate
        self.dtype = compute_dtype
        dev = np.empty(machine.num_devices, object)
        for i, d in enumerate(machine.devices):
            dev[i] = d
        # tp innermost: a stage's tp group is ICI-contiguous, its psums
        # never cross a stage boundary (round 5 — stage-internal TP from
        # the strategy file's pipeline block)
        self.mesh = Mesh(dev.reshape(num_stages, dp, tp),
                         ("stage", "n", "tp"))
        self.block = transformer_block_fn(
            num_heads, causal, tp_axis="tp" if tp > 1 else None)

    # -- params ---------------------------------------------------------

    def init(self, seed: int = 0):
        k0, k1, k2 = jax.random.split(jax.random.PRNGKey(seed), 3)
        blocks = init_block_stack(k0, self.L, self.D, self.F)
        # (L, ...) -> (S, L/S, ...): one leading slice per stage
        blocks = jax.tree.map(
            lambda p: p.reshape((self.S, self.L // self.S) + p.shape[1:]),
            blocks)
        blocks = place_stage_params(blocks, self.mesh,
                                    param_specs=self._block_specs())
        repl = NamedSharding(self.mesh, P())
        scale = 1.0 / jnp.sqrt(jnp.asarray(self.D, "float32"))
        other = {
            "embed": jax.random.normal(k1, (self.V, self.D), "float32")
            * scale,
            "pos": jax.random.normal(k2, (self.seq, self.D), "float32")
            * scale,
            "ln_f": jnp.stack([jnp.ones((self.D,), "float32"),
                               jnp.zeros((self.D,), "float32")]),
            "head_w": jnp.zeros((self.D, self.V), "float32"),
            "head_b": jnp.zeros((self.V,), "float32"),
        }
        other = {k: jax.device_put(v, repl) for k, v in other.items()}
        return {"blocks": blocks, **other}

    # -- forward/loss ---------------------------------------------------

    def _block_specs(self):
        return stage_param_specs(
            "stage", "tp" if self.tp > 1 else None, sub_dims=1)

    def _stage_fn(self, block=None):
        block = block or self.block
        n_sub, dtype = self.L // self.S, self.dtype

        def stage(p, x):
            p = jax.tree.map(lambda q: q.astype(dtype), p)
            for i in range(n_sub):  # static sub-layer loop within a stage
                x = block(jax.tree.map(lambda q: q[i], p), x)
            return x

        return stage

    def _embed(self, params, tokens):
        # gather before casting (f32 scatter-add in the VJP, no full-
        # vocab low-precision table copy)
        return params["embed"][tokens].astype(self.dtype) \
            + params["pos"].astype(self.dtype)[None]

    def _head_loss(self, params, ys, labels):
        """Final-norm + vocab head + shifted masked CE over the
        (M, mb, seq, D) pipeline outputs — shared by the pipelined and
        sequential-reference paths so their semantics cannot drift."""
        y = ys.reshape(self.batch, self.seq, self.D)
        y = _layer_norm(params["ln_f"][0], params["ln_f"][1],
                        y.astype("float32"))
        logits = y @ params["head_w"] + params["head_b"]
        if self.causal:
            labels = jnp.concatenate(
                [labels[:, 1:],
                 jnp.full((labels.shape[0], 1), -1, labels.dtype)], axis=1)
        valid = labels >= 0
        lp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            lp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
        return jnp.sum(jnp.where(valid, nll, 0.0)) \
            / jnp.maximum(valid.sum(), 1)

    def loss_fn(self, params, tokens, labels):
        xs = microbatch(self._embed(params, tokens), self.M)
        ys = spmd_pipeline(self._stage_fn(), params["blocks"], xs,
                           self.mesh, batch_spec=P("n"),
                           param_specs=self._block_specs())
        return self._head_loss(params, ys, labels)

    def loss_reference(self, params, tokens, labels):
        """Same model WITHOUT the pipeline ring (sequential stages, full
        unsharded math — no tp psums) — pins the pipelined semantics in
        tests."""
        xs = microbatch(self._embed(params, tokens), self.M)
        ref_block = transformer_block_fn(self.H, self.causal)
        ys = sequential_reference(self._stage_fn(ref_block),
                                  params["blocks"], xs)
        return self._head_loss(params, ys, labels)

    # -- training -------------------------------------------------------

    def make_train_step(self):
        def step(params, tokens, labels):
            loss, g = jax.value_and_grad(self.loss_fn)(params, tokens,
                                                       labels)
            new = jax.tree.map(lambda p, gr: p - self.lr * gr, params, g)
            return new, loss

        return jax.jit(step, donate_argnums=(0,))
