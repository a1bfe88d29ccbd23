"""Device milliseconds a traced step spends in the plan's regrids
(instructions under ``ff_regrid.<op>.<input>``); 0 under a plan that is
pure data parallelism."""

from benchmarks.program_trace import pass_ms_per_step

METRIC = {"name": "executor.regrid_ms_per_step", "unit": "ms",
          "better": "lower", "source": "device_trace", "layer": "executor",
          "moves": "train_items_per_s_per_chip"}


def read(facts):
    return pass_ms_per_step(facts, "regrid")
