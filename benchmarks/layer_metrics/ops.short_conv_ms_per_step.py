"""Device milliseconds a traced step spends in the gated short
convolutions' operators (``blk<i>_conv``: the product in, the gates and the
depthwise convolution between them, the product out), forward, backward
and the backward pass's recomputed forward together
(``benchmarks/operator_time.py``).  Nothing on a program without operator
names or without such operators."""

import re

from benchmarks.operator_time import operator_seconds

METRIC = {"name": "ops.short_conv_ms_per_step", "unit": "ms",
          "better": "lower", "source": "device_trace", "layer": "ops",
          "moves": "train_items_per_s_per_chip"}

OPERATORS = re.compile(r"^blk\d+_conv$")


def read(facts):
    seconds = operator_seconds(facts, OPERATORS)
    if seconds is None:
        return None
    return 1e3 * seconds / facts["traced_steps"]
