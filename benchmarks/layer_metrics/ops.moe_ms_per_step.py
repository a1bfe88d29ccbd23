"""Device milliseconds a traced step spends in the expert layers'
operators (``blk<i>_moe_router``, ``blk<i>_moe_experts``: dispatch,
grouped products and combine, ``blk<i>_moe_shared``), all passes with the
recomputed forward (``benchmarks/operator_time.py``).  Nothing on a
program without such operators."""

import re

from benchmarks.operator_time import operator_seconds

METRIC = {"name": "ops.moe_ms_per_step", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "ops",
          "moves": "train_items_per_s_per_chip"}

OPERATORS = re.compile(r"^blk\d+_moe_(router|experts|shared)$")


def read(facts):
    seconds = operator_seconds(facts, OPERATORS)
    if seconds is None:
        return None
    return 1e3 * seconds / facts["traced_steps"]
