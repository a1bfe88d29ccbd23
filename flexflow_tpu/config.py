"""Training/run configuration, equivalent of the reference's ``FFConfig``
(config.h:41-56) with CLI parity with ``parse_input_args`` (cnn.cc:539-582)
and ``DefaultConfig`` (cnn.cc:23-35)."""

from __future__ import annotations

import dataclasses
from typing import Sequence

from flexflow_tpu.strategy import Strategy


def _checked_policy(v: str) -> str:
    """Validate an --on-divergence value at parse time (like -delta)."""
    if v not in ("halt", "warn", "rollback"):
        raise SystemExit(
            f"--on-divergence must be halt|warn|rollback, got {v!r}")
    return v


def _checked_fault_spec(v: str) -> str:
    """Validate a --fault-spec string at parse time so a typo'd kind
    fails loudly instead of never firing."""
    from flexflow_tpu.utils.faultinject import FaultSpecError, \
        parse_fault_spec

    try:
        parse_fault_spec(v)
    except FaultSpecError as e:
        raise SystemExit(f"--fault-spec: {e}")
    return v


@dataclasses.dataclass
class FFConfig:
    # DefaultConfig parity (cnn.cc:23-35)
    epochs: int = 10
    batch_size: int = 64
    num_iterations: int = 10
    print_freq: int = 10
    input_height: int = 224
    input_width: int = 224
    learning_rate: float = 0.01
    weight_decay: float = 1e-4
    momentum: float = 0.0
    num_nodes: int = 1
    workers_per_node: int = 0      # -ll:gpu analog; 0 = use all local chips
    loaders_per_node: int = 4      # -ll:cpu analog (data-loader threads)
    profiling: bool = False
    trace_dir: str = ""            # jax.profiler trace output (-lg:prof analog)
    ckpt_dir: str = ""             # checkpoint/resume directory (TPU-native)
    ckpt_freq: int = 0             # save every N iters (0 = final only)
    synthetic_input: bool = True   # reference default when -d absent (README.md:68)
    dataset_path: str = ""
    strategy_file: str = ""
    # Verification mechanisms (SURVEY.md §4 parity)
    params_init: str = "default"   # "ones" = PARAMETER_ALL_ONES (conv_2d.cu:393-398)
    print_intermediates: bool = False  # PRINT_INTERMEDIATE_RESULT (nmt/rnn.h:25)
    dry_compile: bool = False      # DISABLE_COMPUTATION analog (ops.h:19):
                                   # build+partition+compile, execute nothing
    # TPU-native additions
    compute_dtype: str = "float32"   # "bfloat16" for MXU-friendly training
    # mixed-precision policy (perf round): param_dtype is the STORAGE
    # dtype of the parameters ("bfloat16" halves parameter/gradient HBM
    # and collective traffic).  Anything other than float32 switches
    # the optimizer to master-weight mode: a float32 master copy of
    # every parameter lives in the optimizer state, the update runs in
    # float32 against the masters, and the stored params are re-cast
    # from the masters on write-back (checkpoints carry the masters, so
    # resume is bit-exact).  Compute dtype stays an independent knob —
    # the step casts params to compute_dtype before the forward pass.
    param_dtype: str = "float32"
    seed: int = 0
    num_classes: int = 1000
    # run telemetry (obs subsystem): when obs_dir is set, every surface
    # (fit / search / bench) appends structured JSONL records to
    # <obs_dir>/<run_id>.jsonl; unset = telemetry fully disabled (the step
    # loop pays a single predicate check).  run_id defaults to a fresh
    # time+pid id; set it to join several processes into one stream.
    obs_dir: str = ""
    run_id: str = ""
    # size cap of one obs JSONL file before rollover to a numbered
    # sibling (<run>.jsonl.1, .2, ...); 0 = never rotate
    obs_max_bytes: int = 64 * 1024 * 1024
    # always-on live metrics export (obs/metrics.py): when set, fit()
    # atomically rewrites a Prometheus textfile at this path (plus a
    # <path>.json snapshot) at its existing host-sync boundaries —
    # throughput, MFU, HBM peak/live bytes, rollback/fault counters,
    # prefetch stall.  Independent of obs_dir; empty = disabled.
    metrics_path: str = ""
    # sampled per-op timing in fit() (obs/trace.py's measured side): every
    # Nth step the run syncs and times forward/backward/optimizer
    # sections (plus jax.profiler annotations), and isolated per-op shard
    # timings are emitted post-loop — all as op_time records.  0 = off
    # (the default; sampling perturbs the device pipeline on sampled
    # steps).  Requires obs_dir.
    op_time_every: int = 0
    # strategy search (sim/search.py): number of parallel MCMC chains and
    # the delta re-simulation mode — "on" (default), "off" (every proposal
    # pays a full re-simulation) or "check" (delta cross-checked against
    # full, aborting on divergence; debug only)
    search_chains: int = 1
    search_delta: str = "on"
    # execution performance (round 6): the whole-graph regrid planner
    # (parallel/regrid.py) — "on" (default) resolves every
    # producer->consumer reshard once at plan time with coalescing and
    # cost-aware hop selection; "off" keeps the legacy per-trace path
    # (loss-bit-identical — the equivalence tests compare the two).
    regrid_planner: str = "on"
    # heterogeneous placed-op overlap (perf round): "on" (default) fuses
    # independent same-level placed ops that legacy scheduling would
    # dispatch as SEQUENTIAL shard_maps into one grouped dispatch whose
    # body branches on the group axis, so XLA runs the disjoint device
    # blocks concurrently; "off" keeps the legacy one-dispatch-per-op
    # path (loss-bit-identical — the equivalence tests compare the two,
    # mirroring the regrid-planner pattern above).
    placed_overlap: str = "on"
    # double-buffered device prefetch (data/prefetch.py): queue depth of
    # batches staged on device ahead of the training loop; 0 disables
    # (the legacy synchronous pull inside the timed loop)
    prefetch_depth: int = 2
    # fault tolerance (robustness round): what the step health guard does
    # when a loss window turns non-finite — "halt" (raise TrainingDiverged,
    # the default), "warn" (log + obs record, keep training), "rollback"
    # (restore the last VERIFIED checkpoint and continue on fresh data,
    # at most max_rollbacks times).  Checks run only at print/checkpoint
    # boundaries on already-accumulated device losses — zero per-step
    # host syncs (utils/health.py).
    on_divergence: str = "halt"
    max_rollbacks: int = 3
    # deterministic fault injection (utils/faultinject.py), e.g.
    # "loss_nan@120,data_io@50x3,ckpt_truncate@2"; empty = disabled
    fault_spec: str = ""
    # retrying data sources (utils/retry.py): total read/decode attempts
    # per item, and how many permanently-bad items a run may skip before
    # giving up (data/hdf5.py, data/imagenet.py)
    data_retry_attempts: int = 4
    data_skip_budget: int = 16
    # elastic training (utils/elastic.py): --elastic turns permanent
    # device loss into recovery on the surviving mesh (re-search + live
    # regrid, checkpoint fallback) instead of a fatal error; a shrink
    # below --min-devices raises ElasticShrinkRefused instead of limping.
    # --research-budget-s caps the surviving-mesh re-search wall clock;
    # elastic_search_iters its proposal count.
    elastic: bool = False
    min_devices: int = 1
    research_budget_s: float = 30.0
    elastic_search_iters: int = 2000
    # decomposed strategy search (round 19): --decompose makes every
    # re-search (elastic recovery included) run the block-decomposed
    # path — per-layer sub-searches with shared-block memoization and a
    # boundary-refinement pass.  --research-budget-s then caps the
    # TOTAL wall across all sub-searches (one shared deadline), while
    # --block-budget-s additionally caps each sub-search (0 = proposal-
    # count bound only); --boundary-refine-iters reserves proposals for
    # the post-stitch refinement pass (0 = 20% of the budget).
    decompose: bool = False
    block_budget_s: float = 0.0
    boundary_refine_iters: int = 0
    # elastic re-expansion (round 9): after a shrink, previously-dead
    # ordinals are probed at existing boundaries; --regrow-probes
    # consecutive healthy probes trigger recover_grow (debounce), and a
    # run grows back at most --max-regrows times (flapping cap; 0
    # disables re-expansion entirely)
    max_regrows: int = 1
    regrow_probes: int = 2
    # preemption-aware graceful drain: wall budget for committing the
    # final verified checkpoint after SIGTERM/SIGINT (async writer wait,
    # best-effort sync save fallback past the budget)
    drain_budget_s: float = 60.0
    # step watchdog (utils/health.StepWatchdog): hang deadline =
    # hang_factor x rolling per-step estimate, floored at hang_min_s;
    # 0 = watchdog off (the default — no timer threads in healthy runs)
    hang_factor: float = 0.0
    hang_min_s: float = 60.0
    # transient-retry budget window: probe_devices transient verdicts
    # consume a budget of 3; this many CONSECUTIVE healthy steps refill
    # it, so a long run absorbs spread-out hiccups while rapid flapping
    # still exhausts the cap
    transient_reset_steps: int = 16
    # async checkpointing (utils/checkpoint.AsyncCheckpointWriter):
    # serialization/digest/commit on a background writer, at most one
    # save in flight; fit blocks only on the final save and before a
    # rollback restore.  Off by default — the sync path is unchanged.
    ckpt_async: bool = False
    # buffer donation (round 13): "on" (default) threads donate_argnums
    # through every jitted train step — params, optimizer state, and the
    # mixed-precision __master leaves alias their outputs, so the
    # steady-state step allocates only the batch and the loss; "off" is
    # the A/B arm of the bit-identity contract (tests/test_donation.py)
    # and a debug escape for buffer-reuse investigations.  No CLI flag on
    # purpose: donation is a compilation property, not a training knob.
    donate: str = "on"
    # branch-gradient accumulation (round 13): "tree" (default) hands
    # each consumer of a multi-consumer tensor its own alias
    # (ops/fanout.grad_fanout), so the n branch cotangents re-join as
    # one balanced n-ary sum XLA fuses into a single (n+1)-operand pass
    # instead of the profile's chain of 2-operand add_any fusions
    # (3(n-1) -> n+1 HBM traffic units); "off" keeps JAX's pairwise
    # chain.  Bit-identical for fan-out <= 3, reassociates (tolerance-
    # level) beyond.  No CLI flag: a compilation property, like donate.
    grad_fanout: str = "tree"
    # serving runtime (serve/ package, apps/serve.py): --max-batch caps
    # the continuous batcher's decode slots (0 = the model's batch_size);
    # --serve-queue-hi is the queue-depth watermark that triggers a
    # regrow of parked devices; --serve-idle-boundaries is how many
    # consecutive idle decode boundaries trigger a shrink (0 disables
    # autoscaling in that direction)
    max_batch: int = 0
    serve_queue_hi: int = 0
    serve_idle_boundaries: int = 0
    # disaggregated serving (serve/router.py): --serve-prefill-devices
    # > 0 carves the mesh into a prefill pool (the first N devices,
    # split across --serve-prefill-replicas engines searched under the
    # latency objective) and a decode pool (the rest, split across
    # --serve-decode-replicas engines searched under the decode
    # objective); 0 keeps the single-pool engine
    serve_prefill_devices: int = 0
    serve_prefill_replicas: int = 1
    serve_decode_replicas: int = 1
    # fleet coordinator (fleet/ package, apps/fleet.py): --fleet-quantum
    # is how many steps (train iterations / decode boundaries) each
    # running job gets per round-robin turn before the coordinator
    # re-evaluates the packing; --fleet-search-budget-s caps each
    # arbiter pricing re-search's wall clock (generous by default so
    # the fixed iteration bound binds and packing stays reproducible)
    fleet_quantum: int = 4
    fleet_search_budget_s: float = 30.0
    # static plan analyzer (verify/plan.py, round 12): the drivers fail
    # fast on a strategy whose plan check reports errors; --allow-degraded
    # demotes the promoted degradation diagnostics (replicated/normalized
    # execution the machine previously only warned about) back to
    # warnings, restoring the old degrade-and-continue behavior
    allow_degraded: bool = False

    strategies: Strategy = dataclasses.field(default_factory=Strategy)

    def __post_init__(self):
        if self.strategy_file:
            self.load_strategy_file(self.strategy_file)

    # FFConfig::load/save_strategy_file parity (strategy.cc:62-86)
    def load_strategy_file(self, filename: str) -> bool:
        self.strategies = Strategy.load(filename)
        return True

    def save_strategy_file(self, filename: str) -> bool:
        self.strategies.save(filename)
        return True

    @classmethod
    def from_args(cls, argv: Sequence[str]) -> "FFConfig":
        """Parse the reference's flag set (cnn.cc:539-582): -e/--epochs,
        -b/--batch-size, --lr, --wd, -p/--print-freq, -d/--dataset,
        -s/--strategy, plus TPU-native extras (--dtype, --iters, --seed,
        --profiling, -obs-dir/-run-id for the run-telemetry JSONL)."""
        from flexflow_tpu.utils.flags import flag_stream

        cfg = cls()
        for a, val in flag_stream(argv):
            if a in ("-e", "--epochs"):
                cfg.epochs = int(val())
            elif a in ("-b", "--batch-size"):
                cfg.batch_size = int(val())
            elif a in ("--lr", "--learning-rate"):
                cfg.learning_rate = float(val())
            elif a in ("--wd", "--weight-decay"):
                cfg.weight_decay = float(val())
            elif a in ("-p", "--print-freq"):
                cfg.print_freq = int(val())
            elif a in ("-d", "--dataset"):
                cfg.dataset_path = val()
                cfg.synthetic_input = False
            elif a in ("-s", "--strategy"):
                cfg.strategy_file = val()
                cfg.load_strategy_file(cfg.strategy_file)
            elif a == "-ll:gpu":   # accepted for drop-in compatibility
                cfg.workers_per_node = int(val())
            elif a == "-ll:cpu":
                cfg.loaders_per_node = int(val())
            elif a in ("-i", "--iters", "--iterations"):
                cfg.num_iterations = int(val())
            elif a == "--dtype":
                cfg.compute_dtype = val()
            elif a in ("-param-dtype", "--param-dtype"):
                cfg.param_dtype = val()
            elif a == "--seed":
                cfg.seed = int(val())
            elif a == "--profiling":
                cfg.profiling = True
            elif a == "--trace-dir":
                cfg.trace_dir = val()
            elif a in ("-obs-dir", "--obs-dir"):
                cfg.obs_dir = val()
            elif a in ("-run-id", "--run-id"):
                cfg.run_id = val()
            elif a == "--obs-max-bytes":
                cfg.obs_max_bytes = int(val())
            elif a in ("-op-time-every", "--op-time-every"):
                cfg.op_time_every = int(val())
            elif a in ("-metrics-path", "--metrics-path"):
                cfg.metrics_path = val()
            elif a in ("-chains", "--chains"):
                cfg.search_chains = int(val())
            elif a in ("-delta", "--delta"):
                cfg.search_delta = val()
            elif a in ("-regrid-planner", "--regrid-planner"):
                cfg.regrid_planner = val()
            elif a in ("-placed-overlap", "--placed-overlap"):
                cfg.placed_overlap = val()
            elif a in ("-prefetch-depth", "--prefetch-depth"):
                cfg.prefetch_depth = int(val())
            elif a in ("-on-divergence", "--on-divergence"):
                cfg.on_divergence = _checked_policy(val())
            elif a in ("-max-rollbacks", "--max-rollbacks"):
                cfg.max_rollbacks = int(val())
            elif a in ("-fault-spec", "--fault-spec"):
                cfg.fault_spec = _checked_fault_spec(val())
            elif a == "--data-retry-attempts":
                cfg.data_retry_attempts = int(val())
            elif a == "--data-skip-budget":
                cfg.data_skip_budget = int(val())
            elif a == "--elastic":
                cfg.elastic = True
            elif a == "--min-devices":
                cfg.min_devices = int(val())
            elif a == "--research-budget-s":
                cfg.research_budget_s = float(val())
            elif a == "--elastic-search-iters":
                cfg.elastic_search_iters = int(val())
            elif a == "--decompose":
                cfg.decompose = True
            elif a == "--block-budget-s":
                cfg.block_budget_s = float(val())
            elif a == "--boundary-refine-iters":
                cfg.boundary_refine_iters = int(val())
            elif a == "--max-regrows":
                cfg.max_regrows = int(val())
            elif a == "--regrow-probes":
                cfg.regrow_probes = int(val())
            elif a == "--drain-budget-s":
                cfg.drain_budget_s = float(val())
            elif a == "--hang-factor":
                cfg.hang_factor = float(val())
            elif a == "--hang-min-s":
                cfg.hang_min_s = float(val())
            elif a == "--transient-reset-steps":
                cfg.transient_reset_steps = int(val())
            elif a == "--ckpt-async":
                cfg.ckpt_async = True
            elif a == "--max-batch":
                cfg.max_batch = int(val())
            elif a == "--serve-queue-hi":
                cfg.serve_queue_hi = int(val())
            elif a == "--serve-idle-boundaries":
                cfg.serve_idle_boundaries = int(val())
            elif a == "--serve-prefill-devices":
                cfg.serve_prefill_devices = int(val())
            elif a == "--serve-prefill-replicas":
                cfg.serve_prefill_replicas = int(val())
            elif a == "--serve-decode-replicas":
                cfg.serve_decode_replicas = int(val())
            elif a == "--fleet-quantum":
                cfg.fleet_quantum = int(val())
            elif a == "--fleet-search-budget-s":
                cfg.fleet_search_budget_s = float(val())
            elif a == "--allow-degraded":
                cfg.allow_degraded = True
            elif a == "--ckpt-dir":
                cfg.ckpt_dir = val()
            elif a == "--ckpt-freq":
                cfg.ckpt_freq = int(val())
            elif a == "--height":
                cfg.input_height = int(val())
            elif a == "--width":
                cfg.input_width = int(val())
            elif a == "--classes":
                cfg.num_classes = int(val())
            elif a == "--params-ones":
                cfg.params_init = "ones"
            elif a == "--print-intermediates":
                cfg.print_intermediates = True
            elif a == "--dry-compile":
                cfg.dry_compile = True
            # unknown flags are ignored, like the reference parser
        return cfg
