"""Device milliseconds a traced step spends in the Kimi-delta-attention
mixers' operators (``blk<i>_kda_in``: the product in, the convolutions,
the unit rows, decays and write strengths; ``blk<i>_kda_scan``: the
chunked delta rule; ``blk<i>_kda_out``: the gated head norm and the
product out), forward, backward and the backward pass's recomputed
forward together (``benchmarks/operator_time.py``).  Nothing on a
program without operator names or without such operators."""

import re

from benchmarks.operator_time import operator_seconds

METRIC = {"name": "ops.kda_ms_per_step", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "ops",
          "moves": "train_items_per_s_per_chip"}

OPERATORS = re.compile(r"^blk\d+_kda_(in|scan|out)$")


def read(facts):
    seconds = operator_seconds(facts, OPERATORS)
    if seconds is None:
        return None
    return 1e3 * seconds / facts["traced_steps"]
