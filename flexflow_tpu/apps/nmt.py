"""NMT seq2seq training driver — reference executable parity (nmt/nmt.cc:
top_level_task, flags parse_input_args nmt/nmt.cc:235-267: -b batch size,
-l layers, -s sequence length, -h hidden size, -e embed size).

    python -m flexflow_tpu.apps.nmt -b 64 -l 2 -s 20 -h 2048 -e 2048

Extras beyond the reference: --vocab, --iters, --chunk (LSTM steps per
chunk op), --strategy <file>, --pipeline-stages S (generate the stage
strategy: LSTM layer l on device block l%S — the reference's per-op
placement pipelining, nmt/nmt.cc:269-308 — and wavefront-execute it),
--dtype, --seed, and -obs-dir DIR / -run-id ID (run telemetry: append
the structured training event stream — compile, per-step, summary,
sim_drift records — to DIR/<run-id>.jsonl; render it with
``python -m flexflow_tpu.apps.report``).  Data is synthetic random token
pairs (the reference initializes its word tensors with constants,
nmt/rnn.cu:89-126).
"""

from __future__ import annotations

import sys

from flexflow_tpu.machine import MachineModel
from flexflow_tpu.nmt.rnn_model import (RnnConfig, RnnModel,
                                        synthetic_token_batches)
from flexflow_tpu.strategy import Strategy


def parse_args(argv) -> RnnConfig:
    from flexflow_tpu.utils.flags import flag_stream

    cfg = RnnConfig()
    strategy_file = ""
    for a, val in flag_stream(argv):
        if a == "-b":
            cfg.batch_size = int(val())
        elif a == "-l":
            cfg.num_layers = int(val())
        elif a == "-s":
            cfg.seq_length = int(val())
        elif a == "-h":
            cfg.hidden_size = int(val())
        elif a == "-e":
            cfg.embed_size = int(val())
        elif a == "--vocab":
            cfg.vocab_size = int(val())
        elif a in ("-i", "--iters", "--iterations"):
            cfg.num_iterations = int(val())
        elif a == "--chunk":
            cfg.lstm_per_node_length = int(val())
        elif a == "--lr":
            cfg.learning_rate = float(val())
        elif a == "--dtype":
            cfg.compute_dtype = val()
        elif a in ("-param-dtype", "--param-dtype"):
            cfg.param_dtype = val()
        elif a == "--seed":
            cfg.seed = int(val())
        elif a == "--strategy":
            strategy_file = val()
        elif a == "--pipeline-stages":
            cfg._pipeline_stages = int(val())
        elif a == "--params-ones":
            cfg.params_init = "ones"
        elif a == "--print-intermediates":
            cfg.print_intermediates = True
        elif a == "--dry-compile":
            cfg.dry_compile = True
        elif a in ("-obs-dir", "--obs-dir"):
            cfg.obs_dir = val()
        elif a in ("-run-id", "--run-id"):
            cfg.run_id = val()
        elif a in ("-op-time-every", "--op-time-every"):
            cfg.op_time_every = int(val())
        elif a in ("-metrics-path", "--metrics-path"):
            cfg.metrics_path = val()
        elif a in ("-regrid-planner", "--regrid-planner"):
            cfg.regrid_planner = val()
        elif a in ("-prefetch-depth", "--prefetch-depth"):
            cfg.prefetch_depth = int(val())
        elif a in ("-placed-overlap", "--placed-overlap"):
            cfg.placed_overlap = val()
        elif a == "--ckpt-dir":
            cfg.ckpt_dir = val()
        elif a == "--ckpt-freq":
            cfg.ckpt_freq = int(val())
        elif a in ("-on-divergence", "--on-divergence"):
            from flexflow_tpu.config import _checked_policy

            cfg.on_divergence = _checked_policy(val())
        elif a in ("-max-rollbacks", "--max-rollbacks"):
            cfg.max_rollbacks = int(val())
        elif a in ("-fault-spec", "--fault-spec"):
            from flexflow_tpu.config import _checked_fault_spec

            cfg.fault_spec = _checked_fault_spec(val())
        elif a == "--elastic":
            cfg.elastic = True
        elif a == "--min-devices":
            cfg.min_devices = int(val())
        elif a == "--research-budget-s":
            cfg.research_budget_s = float(val())
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
        elif a == "--allow-degraded":
            cfg.allow_degraded = True
        # unknown flags ignored, like the reference parser
    cfg._strategy_file = strategy_file
    return cfg


def main(argv=None, log=print) -> dict:
    argv = list(sys.argv[1:] if argv is None else argv)
    cfg = parse_args(argv)
    machine = MachineModel()
    strategies = None
    if getattr(cfg, "_strategy_file", ""):
        strategies = Strategy.load(cfg._strategy_file)
        # static plan check (verify/plan.py, round 12): fail fast with
        # the diagnostic list instead of build-time ValueErrors or
        # mid-compile tracebacks; --allow-degraded demotes degradation
        # findings back to the old warn-and-continue.  NOTE: the NMT
        # default strategy intentionally PINS the embeds to single
        # devices (nmt/nmt.cc:269-308 parity) — those are honored
        # placements, not degradations, so a clean file passes.
        from flexflow_tpu.verify.plan import check_plan

        check_plan(RnnModel(cfg, machine, None), strategies, machine,
                   allow_degraded=cfg.allow_degraded,
                   label=cfg._strategy_file)
    elif getattr(cfg, "_pipeline_stages", 0):
        from flexflow_tpu.nmt.rnn_model import pipeline_stage_strategy

        strategies = pipeline_stage_strategy(cfg, machine,
                                             cfg._pipeline_stages)
    model = RnnModel(cfg, machine, strategies)
    log(f"NMT: {cfg.num_layers} layers, seq {cfg.seq_length} "
        f"(chunks of {cfg.lstm_per_node_length}), hidden {cfg.hidden_size}, "
        f"embed {cfg.embed_size}, vocab {cfg.vocab_size}, "
        f"batch {cfg.batch_size}, {machine.num_devices} devices")
    data = synthetic_token_batches(machine, cfg.batch_size, cfg.seq_length,
                                   cfg.vocab_size, seed=cfg.seed)
    # the elastic rebuild factory: reconstruct the RNN on a resized mesh
    # under the re-searched strategy (ff_cfg carries the strategies)
    out = model.fit(
        data, log=log,
        rebuild=lambda ff_cfg, m: RnnModel(cfg, m, ff_cfg.strategies))
    if out.get("drained"):
        log(f"drained at iteration {out.get('completed_steps')}; "
            f"exiting 0 (resume from --ckpt-dir to continue)")
    out.pop("params", None)
    out.pop("state", None)
    return out


if __name__ == "__main__":
    from flexflow_tpu.utils.chip import enable_compile_cache

    enable_compile_cache()  # before the first compile
    main()
