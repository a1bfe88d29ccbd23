"""Share of the device's operation seconds in the traced window that lie
in instructions carrying an operator's name, ``ff_update`` or
``ff_regrid.*``: how much of the step the operator table explains."""

from benchmarks.program_trace import PASSES, pass_ms_per_step

METRIC = {"name": "ops.attributed_share", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "ops",
          "moves": "train_items_per_s_per_chip"}


def read(facts):
    by_pass = {p: pass_ms_per_step(facts, p) for p in PASSES}
    total = sum(v or 0.0 for v in by_pass.values())
    if by_pass["other"] is None or not total:
        return None
    return 100.0 * (total - by_pass["other"]) / total
