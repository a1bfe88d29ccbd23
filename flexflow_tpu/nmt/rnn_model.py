"""RnnModel: seq2seq NMT trainer (reference: nmt/rnn.h:100-379,
nmt/rnn.cu:61-336, driver nmt/nmt.cc).

DAG parity (nmt/rnn.cu:298-326): the sequence is chopped into chunks of
``lstm_per_node_length`` steps; each (layer, chunk) LSTM is an independent
op with its own ParallelConfig; hidden state flows chunk -> chunk, outputs
flow layer -> layer; decoder chunk 0 receives the last encoder chunk's
state.  Per-chunk vocab projections share one weight; softmaxDP computes the
chunk loss against the same chunk's dst tokens.

Weight sharing (the reference's SharedVariable with its 2-level hand-rolled
hierarchical allreduce, nmt/rnn.cu:650-703) is expressed by param_key
sharing: jax.grad sums the chunk ops' contributions, and GSPMD emits the
hierarchical reduction over ICI/DCN.

Update rule parity: the reference applies ``w += -0.1 * grad_sum``
(nmt/rnn.cu:684-702, rate -0.1, no normalization).  We keep SGD with the
model's learning rate on the *summed* (not averaged) chunk gradients, and
normalize the loss by total target tokens instead — document once, apply
everywhere (SURVEY.md §7 normalization note)."""

from __future__ import annotations

import dataclasses
from typing import List, Optional

from flexflow_tpu.config import FFConfig
from flexflow_tpu.machine import MachineModel
from flexflow_tpu.model import FFModel
from flexflow_tpu.ops.base import Tensor
from flexflow_tpu.ops.embed import Embed
from flexflow_tpu.ops.lstm import LSTMChunk
from flexflow_tpu.ops.rnn_linear import RnnLinear
from flexflow_tpu.ops.seq import SliceSeq
from flexflow_tpu.ops.softmax_dp import SoftmaxDP
from flexflow_tpu.strategy import ParallelConfig, Strategy


@dataclasses.dataclass
class RnnConfig:
    """nmt/nmt.cc:34-44 defaults."""

    batch_size: int = 64
    num_layers: int = 2
    seq_length: int = 20
    hidden_size: int = 2048
    embed_size: int = 2048
    vocab_size: int = 20 * 1024
    lstm_per_node_length: int = 10   # LSTM_PER_NODE_LENGTH, nmt/rnn.h:23
    learning_rate: float = 0.1       # reference applies rate -0.1 updates
    num_iterations: int = 10
    compute_dtype: str = "float32"
    # parameter storage dtype ("bfloat16" = mixed precision with f32
    # masters in the optimizer state; forwarded to FFConfig)
    param_dtype: str = "float32"
    seed: int = 0
    # verification mechanisms (forwarded to FFConfig; SURVEY.md §4)
    params_init: str = "default"
    print_intermediates: bool = False
    dry_compile: bool = False
    # run telemetry (forwarded to FFConfig; obs subsystem)
    obs_dir: str = ""
    run_id: str = ""
    # sampled per-op timing + live metrics export (MFU-waterfall round)
    op_time_every: int = 0
    metrics_path: str = ""
    # execution performance (forwarded to FFConfig; round 6)
    regrid_planner: str = "on"
    prefetch_depth: int = 2
    placed_overlap: str = "on"
    # fault tolerance (forwarded to FFConfig; robustness round)
    ckpt_dir: str = ""
    ckpt_freq: int = 0
    on_divergence: str = "halt"
    max_rollbacks: int = 3
    fault_spec: str = ""
    # elastic training + async checkpointing (forwarded to FFConfig)
    elastic: bool = False
    min_devices: int = 1
    research_budget_s: float = 30.0
    # decomposed re-search (round 19, forwarded to FFConfig)
    decompose: bool = False
    block_budget_s: float = 0.0
    boundary_refine_iters: int = 0
    ckpt_async: bool = False
    # elastic re-expansion / graceful drain / step watchdog (round 9)
    max_regrows: int = 1
    regrow_probes: int = 2
    drain_budget_s: float = 60.0
    hang_factor: float = 0.0
    hang_min_s: float = 60.0
    transient_reset_steps: int = 16
    # static plan analyzer (verify/plan.py): demote degradation
    # diagnostics to warnings (old degrade-and-continue behavior)
    allow_degraded: bool = False

    @property
    def chunks_per_seq(self) -> int:
        return (self.seq_length + self.lstm_per_node_length - 1) \
            // self.lstm_per_node_length


def default_global_config(cfg: RnnConfig, machine: MachineModel) -> Strategy:
    """set_global_config parity (nmt/nmt.cc:269-308): LSTMs/linear/softmax
    data-parallel over all devices; embeds pinned (src -> device 0,
    dst -> device 1)."""
    s = Strategy()
    n = machine.num_devices
    devs = tuple(range(n))
    npc = cfg.chunks_per_seq
    for i in range(2 * npc):
        pinned = 0 if i < npc else min(1, n - 1)
        s[f"embed{i}"] = ParallelConfig((1,), (pinned,))
    for l in range(cfg.num_layers):
        for j in range(2 * npc):
            s[f"lstm{l}_{j}"] = ParallelConfig((n,), devs)
    for j in range(npc):
        s[f"linear{j}"] = ParallelConfig((1, n), devs)
        s[f"softmax{j}"] = ParallelConfig((n,), devs)
    return s


def pipeline_stage_strategy(cfg: RnnConfig, machine: MachineModel,
                            num_stages: int) -> Strategy:
    """Pipeline-parallel strategy: LSTM layer ``l`` placed on aligned device
    block ``l % num_stages`` (stage = device block — the reference's own
    pipeline representation, per-op-instance device lists in
    nmt/nmt.cc:269-308).  Chunk ops of adjacent layers on different blocks
    form DAG antidiagonals that the placement scheduler merges into
    concurrent shard_map groups (parallel/placement.py): layer l works on
    chunk j while layer l+1 works on chunk j-1 — wavefront/GPipe-style
    pipelining compiled into ONE SPMD step, from a plain strategy file.

    Embeds feed stage 0 and pin to its block; the vocab projections and
    losses stay data-parallel over the whole machine (they consume every
    stage's output)."""
    n = machine.num_devices
    if num_stages < 1 or n % num_stages:
        raise ValueError(
            f"{num_stages} stages do not divide the {n}-device machine")
    per = n // num_stages
    blocks = [tuple(range(g * per, (g + 1) * per))
              for g in range(num_stages)]
    devs = tuple(range(n))
    npc = cfg.chunks_per_seq
    s = Strategy()
    for i in range(2 * npc):
        s[f"embed{i}"] = ParallelConfig((per,), blocks[0])
    for l in range(cfg.num_layers):
        blk = blocks[l % num_stages]
        for j in range(2 * npc):
            s[f"lstm{l}_{j}"] = ParallelConfig((per,), blk)
    for j in range(npc):
        s[f"linear{j}"] = ParallelConfig((1, n), devs)
        s[f"softmax{j}"] = ParallelConfig((n,), devs)
    return s


class RnnModel(FFModel):
    def __init__(self, rnn_config: RnnConfig = None,
                 machine: Optional[MachineModel] = None,
                 strategies: Optional[Strategy] = None):
        self.rnn = rnn_config or RnnConfig()
        machine = machine or MachineModel()
        if strategies is None:
            strategies = default_global_config(self.rnn, machine)
        ff_cfg = FFConfig(
            batch_size=self.rnn.batch_size,
            learning_rate=self.rnn.learning_rate,
            weight_decay=0.0,
            num_iterations=self.rnn.num_iterations,
            compute_dtype=self.rnn.compute_dtype,
            param_dtype=self.rnn.param_dtype,
            seed=self.rnn.seed,
            params_init=self.rnn.params_init,
            print_intermediates=self.rnn.print_intermediates,
            dry_compile=self.rnn.dry_compile,
            obs_dir=self.rnn.obs_dir,
            run_id=self.rnn.run_id,
            op_time_every=self.rnn.op_time_every,
            metrics_path=self.rnn.metrics_path,
            regrid_planner=self.rnn.regrid_planner,
            prefetch_depth=self.rnn.prefetch_depth,
            placed_overlap=self.rnn.placed_overlap,
            ckpt_dir=self.rnn.ckpt_dir,
            ckpt_freq=self.rnn.ckpt_freq,
            on_divergence=self.rnn.on_divergence,
            max_rollbacks=self.rnn.max_rollbacks,
            fault_spec=self.rnn.fault_spec,
            elastic=self.rnn.elastic,
            min_devices=self.rnn.min_devices,
            research_budget_s=self.rnn.research_budget_s,
            decompose=self.rnn.decompose,
            block_budget_s=self.rnn.block_budget_s,
            boundary_refine_iters=self.rnn.boundary_refine_iters,
            ckpt_async=self.rnn.ckpt_async,
            max_regrows=self.rnn.max_regrows,
            regrow_probes=self.rnn.regrow_probes,
            drain_budget_s=self.rnn.drain_budget_s,
            hang_factor=self.rnn.hang_factor,
            hang_min_s=self.rnn.hang_min_s,
            transient_reset_steps=self.rnn.transient_reset_steps,
            allow_degraded=self.rnn.allow_degraded,
            strategies=strategies,
        )
        super().__init__(ff_cfg, machine)
        self._build()

    # ------------------------------------------------------------------

    def _build(self):
        cfg = self.rnn
        npc = cfg.chunks_per_seq
        L = cfg.lstm_per_node_length
        B = cfg.batch_size

        self.src_tokens = self.create_input((B, cfg.seq_length), "int32",
                                            "src_tokens")
        self.dst_tokens = self.create_input((B, cfg.seq_length), "int32",
                                            "dst_tokens")

        def pc(name, ndims):
            return self._pc(name, ndims)

        # chunk slices (reference: per-chunk word regions, nmt/rnn.cu:89-126)
        srcs, dsts = [], []
        for i in range(npc):
            start = i * L
            length = min(L, cfg.seq_length - start)
            srcs.append(self._add(SliceSeq(
                f"src_chunk{i}", pc(f"src_chunk{i}", 1), self.src_tokens,
                start, length)))
            dsts.append(self._add(SliceSeq(
                f"dst_chunk{i}", pc(f"dst_chunk{i}", 1), self.dst_tokens,
                start, length)))

        # embeddings: chunks share srcEmbed / dstEmbed tables
        embeds: List[Tensor] = []
        for i in range(2 * npc):
            tok = srcs[i] if i < npc else dsts[i - npc]
            key = "srcEmbed" if i < npc else "dstEmbed"
            embeds.append(self._add(Embed(
                f"embed{i}", pc(f"embed{i}", 1), tok,
                cfg.vocab_size, cfg.embed_size, param_key=key,
                compute_dtype=cfg.compute_dtype)))

        # LSTM grid: lstm[layer][chunk] (nmt/rnn.cu:298-318)
        lstm_out = [[None] * (2 * npc) for _ in range(cfg.num_layers)]
        lstm_ops = [[None] * (2 * npc) for _ in range(cfg.num_layers)]
        for i in range(cfg.num_layers):
            for j in range(2 * npc):
                x = embeds[j] if i == 0 else lstm_out[i - 1][j]
                if j == 0:
                    hx = cx = None  # zero initial state (zero[i], rnn.cu:127)
                else:
                    prev = lstm_ops[i][j - 1]
                    hx, cx = prev.hy, prev.cy
                key = f"encoder{i}" if j < npc else f"decoder{i}"
                op = LSTMChunk(f"lstm{i}_{j}", pc(f"lstm{i}_{j}", 1),
                               x, hx, cx, cfg.hidden_size, param_key=key)
                self.layers.append(op)
                lstm_ops[i][j] = op
                lstm_out[i][j] = op.output

        # vocab projection + per-chunk DP softmax loss (decoder side)
        self.loss_ops = []
        for j in range(npc):
            logit = self._add(RnnLinear(
                f"linear{j}", pc(f"linear{j}", 2),
                lstm_out[cfg.num_layers - 1][npc + j],
                cfg.vocab_size, param_key="linear"))
            sm = SoftmaxDP(f"softmax{j}", pc(f"softmax{j}", 1),
                           logit, dsts[j])
            self.layers.append(sm)
            self.loss_ops.append(sm)

        for op in self.layers:
            op.validate_partitioning()

    # ------------------------------------------------------------------

    def loss_fn(self, params, state, src, dst, train: bool = True):
        """Mean NLL per target token over all decoder chunks."""
        inputs = {self.src_tokens.tid: src, self.dst_tokens.tid: dst}
        values, new_state = self.apply(params, state, inputs, train)
        total = 0.0
        for op in self.loss_ops:
            xs = (values[op.output.tid], values[op.labels_tensor.tid])
            with self._op_scope(op, xs):
                total = total + op.loss(*xs)
        ntokens = self.rnn.batch_size * self.rnn.seq_length
        return total / ntokens, new_state

    def make_train_step(self):
        """Plain SGD on summed chunk grads (reference rate*grad updates,
        nmt/rnn.cu:684-702) — shared factory in FFModel."""
        return self.make_sgd_step(self.rnn.learning_rate)

    def init_opt_state(self, params):
        # plain SGD carries no momentum buffers; mixed-precision mode
        # still needs the float32 masters (None in float32 mode)
        return self.master_opt_state(params)

    def fit(self, data_iter, num_iterations: Optional[int] = None,
            warmup: int = 1, log=print, rebuild=None):
        out = super().fit(data_iter,
                          num_iterations or self.rnn.num_iterations,
                          warmup, log, rebuild=rebuild)
        out["sentences_per_sec"] = out["images_per_sec"]
        return out


def synthetic_token_batches(machine: MachineModel, batch_size: int,
                            seq_length: int, vocab_size: int, seed: int = 0):
    """Random (src, dst) token pairs, batch-sharded (reference inits word
    tensors with a constant; random avoids degenerate instant
    memorization)."""
    from flexflow_tpu.data import synthetic_token_stream

    return synthetic_token_stream(machine, batch_size, seq_length,
                                  vocab_size, seed, streams=2)
