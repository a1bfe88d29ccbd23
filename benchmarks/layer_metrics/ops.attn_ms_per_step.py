"""Device milliseconds a traced step spends in the attention operators
(``blk<i>_attn_full`` and ``blk<i>_attn_window``: the q, k and v
products, rotary positions where a layer has them, k and v repeated for
their groups, the flash kernels and the product out), forward, backward
and the backward pass's recomputed forward together
(``benchmarks/operator_time.py``).  Nothing on a program without operator
names or without such operators."""

import re

from benchmarks.operator_time import operator_seconds

METRIC = {"name": "ops.attn_ms_per_step", "unit": "ms",
          "better": "lower", "source": "device_trace", "layer": "ops",
          "moves": "train_items_per_s_per_chip"}

OPERATORS = re.compile(r"^blk\d+_attn_(full|window)$")


def read(facts):
    seconds = operator_seconds(facts, OPERATORS)
    if seconds is None:
        return None
    return 1e3 * seconds / facts["traced_steps"]
