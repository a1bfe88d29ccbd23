"""Transformer LM training driver (BERT-base encoder or GPT-style causal
decoder, optionally MoE) — completes the driver set for the BASELINE.json
config "Transformer/BERT-base via linear+softmax ops, full SOAP strategy
search".  New model capability beyond the reference (which predates
transformers); flags follow the house style of the reference parsers
(cnn.cc:539-582).

    python -m flexflow_tpu.apps.lm --causal -b 16 -s 512 -l 12 \
        --d-model 768 --heads 12 --d-ff 3072 --vocab 32768
    python -m flexflow_tpu.apps.lm --experts 8 --strategy moe.json
    python -m flexflow_tpu.apps.lm --model-config \
        benchmarks/configs/moonlight_16b_a3b.json --preset rehearsal -b 2 -s 32
    python -m flexflow_tpu.apps.lm --model-config \
        benchmarks/configs/granite_4_0_h_micro.json --preset rehearsal -b 2
    python -m flexflow_tpu.apps.lm --model-config \
        benchmarks/configs/laguna_s_2_1.json --preset rehearsal -b 2 -s 32
    python -m flexflow_tpu.apps.lm --model-config \
        benchmarks/configs/lfm2_8b_a1b.json --preset rehearsal -b 2 -s 32
    python -m flexflow_tpu.apps.lm --model-config \
        benchmarks/configs/smallthinker_21b_a3b.json --preset rehearsal \
        -b 2 -s 32
    python -m flexflow_tpu.apps.lm --model-config \
        benchmarks/configs/ling_3_0_flash.json --preset rehearsal -b 2 -s 32

``--model-config`` names a file of a public ``config.json``'s keys
(``model_type`` ``deepseek_v3``: latent attention and expert layers,
``models/latent_moe.py``; ``granitemoehybrid``: Mamba-2 and grouped-query
attention layers, ``models/hybrid_ssm.py``; ``laguna``: sliding-window and
full attention layers over a softmax top-k expert layer,
``models/laguna.py``; ``lfm2_moe``: gated short convolutions and
grouped-query attention under q/k norms over a sigmoid top-k expert layer
with a selection bias, a tied head, ``models/lfm2.py``; a file without a
``model_type`` by its ``model_name``: ``smallthinker_21b_instruct``,
NoPE full and rotary windowed attention over ReGLU experts routed before
attention, ``models/smallthinker.py``; ``bailing_hybrid``: Kimi delta
attention and latent attention layers over a sigmoid router limited to
groups, ``models/ling_hybrid.py``; ``benchmarks/builders/
model_config_lm.py`` builds any of them for the benchmark through
``model_of_config``; ``--preset`` lays one of the file's own named groups
of keys over it); without it the flags
describe a ``TransformerLM``.  Data is synthetic random tokens; labels are
the tokens themselves (causal models learn next-token prediction via the
internal shift; see TransformerLM).
"""

from __future__ import annotations

import sys

from flexflow_tpu.machine import MachineModel
from flexflow_tpu.models.transformer import TransformerConfig, TransformerLM
from flexflow_tpu.strategy import Strategy


def parse_args(argv) -> TransformerConfig:
    from flexflow_tpu.utils.flags import flag_stream

    cfg = TransformerConfig()
    strategy_file = ""
    for a, val in flag_stream(argv):
        if a == "-b":
            cfg.batch_size = int(val())
        elif a in ("-s", "--seq"):
            cfg.seq_length = int(val())
        elif a in ("-l", "--layers"):
            cfg.num_layers = int(val())
        elif a == "--d-model":
            cfg.d_model = int(val())
        elif a == "--heads":
            cfg.num_heads = int(val())
        elif a == "--d-ff":
            cfg.d_ff = int(val())
        elif a == "--vocab":
            cfg.vocab_size = int(val())
        elif a == "--causal":
            cfg.causal = True
        elif a == "--experts":
            cfg.num_experts = int(val())
        elif a == "--moe-every":
            cfg.moe_every = int(val())
        elif a == "--moe-top-k":
            cfg.moe_top_k = int(val())
        elif a in ("-i", "--iters", "--iterations"):
            cfg.num_iterations = int(val())
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
        elif a == "--model-config":
            cfg._model_config = val()
        elif a == "--preset":
            cfg._preset = val()
        elif a == "--params-ones":
            cfg.params_init = "ones"
        elif a == "--print-intermediates":
            cfg.print_intermediates = True
        elif a == "--dry-compile":
            cfg.dry_compile = True
        elif a == "--pipeline-stages":
            cfg._pipeline_stages = int(val())
        elif a == "--microbatches":
            cfg._microbatches = int(val())
        elif a == "--pipeline-tp":
            cfg._pipeline_tp = int(val())
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


def synthetic_lm_batches(machine: MachineModel, batch_size: int,
                         seq_length: int, vocab_size: int, seed: int = 0):
    """Random token batches, batch-sharded; labels = tokens (TransformerLM
    shifts internally for causal models)."""
    from flexflow_tpu.data import synthetic_token_stream

    for (toks,) in synthetic_token_stream(machine, batch_size, seq_length,
                                          vocab_size, seed, streams=1):
        yield toks, toks


def _per_op_tp(strategies, cfg) -> int:
    """Stage-internal TP degree implied by a strategy file's per-op
    entries, for pipeline blocks that predate the explicit "tp" field:
    the head-axis split of ATTENTION entries' rank-3 grids
    ("s", "h", "n") — identified by the op NAME (the LM builder names
    them "blkN_attn"), because a bare grid is ambiguous (MoE grids are
    also rank 3, ("e", "c", "n"), and an expert/capacity split must not
    be misread as head TP).  Accepted when it divides the model's heads
    and d_ff and every attention entry agrees; otherwise 1 (pure
    PP x DP, the round-4 behavior)."""
    # EVERY rank-3 attention entry votes, including unsplit ones — a file
    # mixing split and unsplit attention grids is ambiguous and must not
    # silently derive tp from the split subset (round-6 ADVICE)
    splits = {pc.dims[1] for name, pc in strategies.items()
              if "attn" in name and len(pc.dims) == 3}
    if len(splits) != 1:
        return 1
    tp = splits.pop()
    if tp <= 1 or cfg.num_heads % tp or cfg.d_ff % tp:
        return 1
    return tp


def _main_pipelined(cfg, machine, log) -> dict:
    """--pipeline-stages path: GPipe microbatch pipelining (PP x DP) of
    the block stack via parallel.pipeline.PipelinedLM."""
    import time

    from flexflow_tpu.parallel.pipeline import PipelinedLM

    tp = getattr(cfg, "_pipeline_tp", 0) or 1
    model = PipelinedLM(
        machine, cfg._pipeline_stages,
        getattr(cfg, "_microbatches", 0) or cfg._pipeline_stages,
        num_layers=cfg.num_layers, d_model=cfg.d_model,
        num_heads=cfg.num_heads, d_ff=cfg.d_ff,
        vocab_size=cfg.vocab_size, seq_length=cfg.seq_length,
        batch_size=cfg.batch_size, causal=cfg.causal,
        learning_rate=cfg.learning_rate, compute_dtype=cfg.compute_dtype,
        tp=tp)
    log(f"LM pipeline: {cfg.num_layers} layers over {model.S} stages x "
        f"{machine.num_devices // (model.S * model.tp)} dp x {model.tp} "
        f"tp, {model.M} microbatches, batch {cfg.batch_size}, seq "
        f"{cfg.seq_length}")
    params = model.init(cfg.seed)
    step = model.make_train_step()
    data = synthetic_lm_batches(machine, cfg.batch_size, cfg.seq_length,
                                cfg.vocab_size, seed=cfg.seed)
    losses = []
    toks, labs = next(data)
    params, loss = step(params, toks, labs)  # iteration 1 = compile + warm
    losses.append(float(loss))
    n_timed = cfg.num_iterations - 1
    t0 = time.perf_counter()
    for _ in range(n_timed):
        toks, labs = next(data)
        params, loss = step(params, toks, labs)
        losses.append(loss)
    losses = [float(l) for l in losses]
    elapsed = time.perf_counter() - t0
    tput = (n_timed * cfg.batch_size / elapsed
            if n_timed and elapsed > 0 else 0.0)
    log(f"time = {elapsed:.4f}s, tp = {tput:.2f} images/s")
    return {"loss": losses, "images_per_sec": tput,
            "tokens_per_sec": tput * cfg.seq_length, "elapsed_s": elapsed}


def _latent_moe(config, over):
    from flexflow_tpu.models.latent_moe import LatentMoEConfig, LatentMoELM

    t = LatentMoEConfig.from_config(config, **over)
    return t, LatentMoELM, (
        f"{t.num_layers} blocks, hidden {t.hidden_size}, experts "
        f"[{t.experts_held[0]}, {t.experts_held[1]}) of "
        f"{t.router_outputs} held")


def _hybrid_ssm(config, over):
    from flexflow_tpu.models.hybrid_ssm import HybridSSMConfig, HybridSSMLM

    t = HybridSSMConfig.from_config(config, **over)
    return t, HybridSSMLM, (
        f"{len(t.layer_types)} blocks ({t.layer_types.count('mamba')} "
        f"mamba, {t.layer_types.count('attention')} attention), hidden "
        f"{t.hidden_size}, {t.num_attention_heads} query heads on "
        f"{t.num_key_value_heads}, chunk {t.mamba_chunk_size}")


def _laguna(config, over):
    from flexflow_tpu.models.laguna import LagunaConfig, LagunaLM

    t = LagunaConfig.from_config(config, **over)
    kinds = t.layer_types[:t.num_layers]
    return t, LagunaLM, (
        f"{t.num_layers} blocks ({kinds.count('sliding_attention')} of "
        f"window {t.sliding_window}, {kinds.count('full_attention')} full), "
        f"hidden {t.hidden_size}, "
        f"{sorted(set(t.num_attention_heads_per_layer[:t.num_layers]))} "
        f"query heads on {t.num_key_value_heads}, experts "
        f"[{t.experts_held[0]}, {t.experts_held[1]}) of "
        f"{t.router_outputs} held")


def _lfm2(config, over):
    from flexflow_tpu.models.lfm2 import Lfm2Config, Lfm2LM

    t = Lfm2Config.from_config(config, **over)
    kinds = t.layer_types[:t.num_layers]
    return t, Lfm2LM, (
        f"{t.num_layers} blocks ({kinds.count('conv')} conv of "
        f"{t.conv_L_cache} taps, {kinds.count('full_attention')} "
        f"attention), hidden {t.hidden_size}, {t.num_attention_heads} "
        f"query heads on {t.num_key_value_heads}, "
        f"{min(t.num_dense_layers, t.num_layers)} dense, experts "
        f"[{t.experts_held[0]}, {t.experts_held[1]}) of "
        f"{t.router_outputs} held")


def _smallthinker(config, over):
    from flexflow_tpu.models.smallthinker import (SmallThinkerConfig,
                                                  SmallThinkerLM)

    t = SmallThinkerConfig.from_config(config, **over)
    windowed = sum(t.sliding_window_layout[:t.num_layers])
    return t, SmallThinkerLM, (
        f"{t.num_layers} blocks ({windowed} of window "
        f"{t.sliding_window_size}, {t.num_layers - windowed} full, "
        f"{t.num_layers - sum(t.rope_layout[:t.num_layers])} without "
        f"rotary), hidden {t.hidden_size}, {t.num_attention_heads} query "
        f"heads on {t.num_key_value_heads}, experts "
        f"[{t.experts_held[0]}, {t.experts_held[1]}) of "
        f"{t.router_outputs} held")


def _ling(config, over):
    from flexflow_tpu.models.ling_hybrid import LingHybridConfig, LingHybridLM

    t = LingHybridConfig.from_config(config, **over)
    mla = sum(t.is_mla(i) for i in range(t.num_layers))
    return t, LingHybridLM, (
        f"{t.num_layers} blocks ({t.num_layers - mla} KDA, {mla} MLA; "
        f"{min(t.first_k_dense_replace, t.num_layers)} dense), hidden "
        f"{t.hidden_size}, {t.num_attention_heads} heads, experts "
        f"[{t.experts_held[0]}, {t.experts_held[1]}) of "
        f"{t.router_outputs} held, top {t.num_experts_per_tok} in "
        f"{t.topk_group} of {t.n_group} groups")


#: ``model_type`` of a configuration file -> the class that builds it
MODEL_TYPES = {"deepseek_v3": _latent_moe, "granitemoehybrid": _hybrid_ssm,
               "laguna": _laguna, "lfm2_moe": _lfm2,
               "bailing_hybrid": _ling}
#: ``model_name`` of a file that has no ``model_type`` (SmallThinker's
#: ``config.json``) -> the class that builds it
MODEL_NAMES = {"smallthinker_21b_instruct": _smallthinker}


def model_of_config(config: dict):
    """The key a configuration file is dispatched on and its builder:
    ``model_type`` in ``MODEL_TYPES``, or ``model_name`` in
    ``MODEL_NAMES`` where the file has no ``model_type``.  A builder
    takes the file and fields laid over it and gives the model's config,
    its class and a line saying what it builds."""
    by, table = ("model_type", MODEL_TYPES) if "model_type" in config \
        else ("model_name", MODEL_NAMES)
    build = table.get(config.get(by))
    if build is None:
        raise SystemExit(f"--model-config: {by} {config.get(by)!r}; this "
                         f"driver builds {sorted(table)} files only")
    return by, build


def _main_model_config(cfg, argv, machine, log) -> dict:
    """--model-config path: a configuration file of a public
    ``config.json``'s keys through the model class of its ``model_type``
    and ``FFModel.fit``.  The file gives the model; only the flags the
    command line really carries (-b, -s, -i, --lr, --dtype, --seed,
    -obs-dir) lie over it."""
    import json

    with open(cfg._model_config) as f:
        config = json.load(f)
    preset = getattr(cfg, "_preset", "")
    if preset:
        config.update(config[preset])
    by, build = model_of_config(config)
    if getattr(cfg, "_strategy_file", "") \
            or getattr(cfg, "_pipeline_stages", 0):
        raise SystemExit("--model-config takes no --strategy or "
                         "--pipeline-stages yet (its operators run on the "
                         "grid (1, ..) only)")
    given = set(argv)
    over = {"num_iterations": cfg.num_iterations}
    for flags, key, value in (
            (("-b",), "batch_size", cfg.batch_size),
            (("-s", "--seq"), "seq_length", cfg.seq_length),
            (("--lr",), "learning_rate", cfg.learning_rate),
            (("--dtype",), "compute_dtype", cfg.compute_dtype),
            (("--seed",), "seed", cfg.seed)):
        if given & set(flags):
            over[key] = value
    if cfg.obs_dir:
        over["ff"] = {"obs_dir": cfg.obs_dir, "run_id": cfg.run_id}
    if machine.num_devices > 1:
        import jax

        log(f"--model-config: the model's operators run on the grid "
            f"(1, ..) only; training on one of {machine.num_devices} "
            f"devices")
        machine = MachineModel(jax.devices()[:1])
    t, model_class, what = build(config, over)
    if t.seq_length > int(config.get("max_position_embeddings",
                                     t.seq_length)):
        raise SystemExit(f"{t.seq_length} positions, the configuration has "
                         f"{config['max_position_embeddings']}")
    model = model_class(t, machine)
    log(f"LM: {config.get('name', cfg._model_config)} (by {by} "
        f"{config[by]}), {what}, seq "
        f"{t.seq_length}, vocab {t.vocab_size}, batch {t.batch_size}, "
        f"{machine.num_devices} devices")
    data = synthetic_lm_batches(machine, t.batch_size, t.seq_length,
                                t.vocab_size, seed=t.seed)
    out = model.fit(data, log=log,
                    rebuild=lambda ff_cfg, m: model_class(t, m))
    out["tokens_per_sec"] = (out.get("images_per_sec") or 0.0) \
        * t.seq_length
    out.pop("params", None)
    out.pop("state", None)
    return out


def main(argv=None, log=print) -> dict:
    argv = list(sys.argv[1:] if argv is None else argv)
    cfg = parse_args(argv)
    machine = MachineModel()
    if getattr(cfg, "_model_config", ""):
        return _main_model_config(cfg, argv, machine, log)
    sf = getattr(cfg, "_strategy_file", "")
    loaded_strategies = Strategy.load(sf) if sf else None
    if loaded_strategies is not None:
        # static plan check (verify/plan.py, round 12): a shadow model
        # built without the strategy vets per-op legality, the
        # __pipeline__ block, and the per-device HBM fit as one
        # diagnostic list — SystemExit(2) on errors instead of
        # build-time ValueErrors / mid-compile tracebacks;
        # --allow-degraded keeps the old degrade-and-continue behavior
        from flexflow_tpu.verify.plan import check_plan

        check_plan(TransformerLM(cfg, machine, None), loaded_strategies,
                   machine, allow_degraded=cfg.allow_degraded, label=sf)
    if loaded_strategies is not None \
            and not getattr(cfg, "_pipeline_stages", 0) \
            and not getattr(cfg, "_microbatches", 0):
        # a searcher-emitted pipeline block in the strategy file drives
        # the GPipe path exactly like the flags (round 4, VERDICT r3 #5:
        # stage/microbatch counts live in the strategy artifact, not only
        # in driver flags); EITHER explicit pipeline flag disables the
        # block wholesale (no partial merging of file and flags)
        pp = loaded_strategies.pipeline
        if pp and pp["stages"] > 1:
            cfg._pipeline_stages = pp["stages"]
            cfg._microbatches = pp["microbatches"]
            # stage-internal TP (round 5, VERDICT r4 #5): the block's own
            # tp if the searcher emitted one; otherwise derived from the
            # file's per-op entries (the head-axis split of any 3-dim
            # attention grid) — per-op TP entries now EXECUTE alongside
            # the pipeline instead of being dropped
            tp = int(pp.get("tp", 1) or 1)
            if tp == 1:
                tp = _per_op_tp(loaded_strategies, cfg)
            cfg._pipeline_tp = tp
            cfg._strategy_file = ""
            log(f"pipeline block from {sf}: {pp['stages']} stages x "
                f"{pp['microbatches']} microbatches"
                + (f" x tp={tp} (stage-internal TP from the strategy "
                   f"file)" if tp > 1 else "")
                + " (file-driven GPipe)")
        elif pp:
            # a hand-edited stages<=1 block would previously clear the
            # strategy file and then fail the >1 gate below — silently
            # dropping BOTH the pipeline and the per-op entries (round-4
            # ADVICE): keep the file, ignore the block, and say so
            log(f"warning: __pipeline__ block in {sf} has stages="
                f"{pp['stages']} <= 1 — ignored; per-op entries kept")
    if getattr(cfg, "_pipeline_stages", 0) > 1:
        unsupported = [flag for flag, on in (
            ("--strategy", bool(getattr(cfg, "_strategy_file", ""))),
            ("--experts", cfg.num_experts > 0),
            ("--dry-compile", cfg.dry_compile),
            ("--params-ones", cfg.params_init == "ones"),
            ("--print-intermediates", cfg.print_intermediates),
        ) if on]
        if unsupported:
            raise SystemExit(
                f"--pipeline-stages does not support: "
                f"{', '.join(unsupported)} (the pipelined path trains a "
                f"homogeneous dense block stack outside the op DAG)")
        return _main_pipelined(cfg, machine, log)
    strategies = loaded_strategies \
        if getattr(cfg, "_strategy_file", "") else None
    model = TransformerLM(cfg, machine, strategies)
    moe = (f", {cfg.num_experts} experts/{cfg.moe_every} blocks"
           if cfg.num_experts else "")
    log(f"LM: {'causal' if cfg.causal else 'encoder'}, {cfg.num_layers} "
        f"layers, d_model {cfg.d_model}, {cfg.num_heads} heads, d_ff "
        f"{cfg.d_ff}, seq {cfg.seq_length}, vocab {cfg.vocab_size}, batch "
        f"{cfg.batch_size}{moe}, {machine.num_devices} devices")
    data = synthetic_lm_batches(machine, cfg.batch_size, cfg.seq_length,
                                cfg.vocab_size, seed=cfg.seed)
    # the elastic rebuild factory: reconstruct the LM on a resized mesh
    # under the re-searched strategy (ff_cfg carries the strategies)
    out = model.fit(
        data, log=log,
        rebuild=lambda ff_cfg, m: TransformerLM(cfg, m,
                                                ff_cfg.strategies))
    if out.get("drained"):
        log(f"drained at iteration {out.get('completed_steps')}; "
            f"exiting 0 (resume from --ckpt-dir to continue)")
    out["tokens_per_sec"] = (out.get("images_per_sec") or 0.0) \
        * cfg.seq_length
    if out["tokens_per_sec"]:
        log(f"tokens/s = {out['tokens_per_sec']:.0f}")
    out.pop("params", None)
    out.pop("state", None)
    return out


if __name__ == "__main__":
    from flexflow_tpu.utils.chip import enable_compile_cache

    enable_compile_cache()  # before the first compile
    main()
