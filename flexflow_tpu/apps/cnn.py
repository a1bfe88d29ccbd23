"""CNN training driver — reference executable parity (cnn.cc:43-135
top_level_task + parse_input_args cnn.cc:539-582).

    python -m flexflow_tpu.apps.cnn alexnet -b 64 --lr 0.01 -i 10
    python -m flexflow_tpu.apps.cnn inception -d /data/imagenet -s strat.pb

Flags are FFConfig.from_args (reference -e/-b/--lr/--wd/-p/-d/-s set, plus
TPU-native extras).  With no ``-d`` the input is synthetic, exactly like the
reference (README.md:68); ``-d`` accepts an ImageNet-style directory or a
comma-separated list of HDF5 batch files (the legacy loader's format).
Prints the reference's metric line: ``time = %.4fs, tp = %.2f images/s``.
"""

from __future__ import annotations

import sys

from flexflow_tpu.config import FFConfig
from flexflow_tpu.machine import MachineModel

MODELS = {}


def _builders():
    global MODELS
    if not MODELS:
        from flexflow_tpu import models as zoo

        MODELS = {
            "alexnet": zoo.build_alexnet,
            "vgg16": zoo.build_vgg16,
            "vgg": zoo.build_vgg16,
            "inception": zoo.build_inception_v3,
            "inception_v3": zoo.build_inception_v3,
            "resnet101": zoo.build_resnet101,
            "resnet": zoo.build_resnet101,
            "densenet121": zoo.build_densenet121,
            "densenet": zoo.build_densenet121,
        }
    return MODELS


def make_data(cfg: FFConfig, machine: MachineModel, dataset=None,
              olog=None):
    """Choose the input source the way the reference does: synthetic unless
    -d was given (cnn.cc:79, README.md:68).  File-backed sources run
    under the retrying/skipping fault-tolerance layer and report
    ``data_fault``/``recovery`` records on ``olog`` (caller-owned)."""
    from flexflow_tpu.data import (hdf5_batches, image_batches,
                                   synthetic_batches)

    if cfg.synthetic_input or not cfg.dataset_path:
        return synthetic_batches(machine, cfg.batch_size, cfg.input_height,
                                 cfg.input_width, num_classes=cfg.num_classes,
                                 mode="random", seed=cfg.seed)
    if cfg.dataset_path.endswith((".h5", ".hdf5")):
        return hdf5_batches(machine, cfg.dataset_path.split(","),
                            cfg.batch_size, olog=olog,
                            retry_attempts=cfg.data_retry_attempts,
                            skip_budget=cfg.data_skip_budget)
    return image_batches(machine, dataset, cfg.batch_size, cfg.input_height,
                         cfg.input_width, num_threads=cfg.loaders_per_node,
                         shuffle_seed=cfg.seed, olog=olog,
                         retry_attempts=cfg.data_retry_attempts,
                         skip_budget=cfg.data_skip_budget)


def main(argv=None, log=print) -> dict:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0].startswith("-"):
        model_name = "alexnet"
    else:
        model_name = argv.pop(0)
    builders = _builders()
    if model_name not in builders:
        raise SystemExit(
            f"unknown model {model_name!r}; choose from "
            f"{sorted(set(builders))}")
    cfg = FFConfig.from_args(argv)
    machine = MachineModel()

    # Scan a directory dataset BEFORE building the model so the classifier
    # head matches the data: labels >= num_classes would silently clamp in
    # the gathered cross-entropy instead of erroring under jit.
    dataset = None
    if cfg.dataset_path and not cfg.synthetic_input \
            and not cfg.dataset_path.endswith((".h5", ".hdf5")):
        from flexflow_tpu.data import ImageDataset

        dataset = ImageDataset(cfg.dataset_path, "train")
        if "--classes" in argv:
            if dataset.num_classes > cfg.num_classes:
                raise SystemExit(
                    f"--classes {cfg.num_classes} but dataset has "
                    f"{dataset.num_classes} class directories")
        else:
            cfg.num_classes = dataset.num_classes

    if cfg.strategies:
        # static plan check (verify/plan.py, round 12): vet the strategy
        # against a shadow model built WITHOUT it, so rank/divisibility
        # defects become a diagnostic list here instead of build-time
        # ValueErrors or mid-compile tracebacks below; SystemExit(2) on
        # errors, --allow-degraded keeps the old degrade-and-continue
        import dataclasses as _dc

        from flexflow_tpu.strategy import Strategy as _Strategy
        from flexflow_tpu.verify.plan import check_plan

        shadow_cfg = _dc.replace(cfg, strategies=_Strategy(),
                                 strategy_file="")
        check_plan(builders[model_name](shadow_cfg, machine),
                   cfg.strategies, machine,
                   allow_degraded=cfg.allow_degraded,
                   label=cfg.strategy_file or "strategies")
    ff = builders[model_name](cfg, machine)
    log(ff.summary())
    # the data surface's obs sink: file-backed sources emit data_fault /
    # recovery / thread_leak records here (same run id as the fit stream
    # when -run-id is set, so report renders them as one run)
    from flexflow_tpu import obs

    data_olog = obs.from_config(cfg, surface="data")
    try:
        data = make_data(cfg, machine, dataset, olog=data_olog)
        # the builder doubles as the elastic rebuild factory: on
        # permanent device loss (--elastic) fit() reconstructs the graph
        # on the surviving mesh through it (utils/elastic.py)
        out = ff.fit(data, log=log, rebuild=builders[model_name])
    finally:
        data_olog.close()
    if out.get("drained"):
        # graceful preemption drain: the run stopped cleanly with a
        # verified checkpoint; exit 0 is the scheduler contract (a
        # non-zero exit here would be retried as a FAILURE)
        log(f"drained at iteration {out.get('completed_steps')}; "
            f"exiting 0 (resume from --ckpt-dir to continue)")
    out.pop("params", None)
    out.pop("state", None)
    return out


if __name__ == "__main__":
    from flexflow_tpu.utils.chip import enable_compile_cache

    enable_compile_cache()  # before the first compile
    main()
    sys.exit(0)
