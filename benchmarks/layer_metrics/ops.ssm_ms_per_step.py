"""Device milliseconds a traced step spends in the state-space mixers'
operators (``blk<i>_ssm_in``: projection, convolution, activations;
``blk<i>_ssm_scan``: the scan; ``blk<i>_ssm_out``: gated norm and output
projection), forward, backward and the backward pass's recomputed forward
together (``benchmarks/operator_time.py``).  Nothing on a program without
operator names or without such operators."""

import re

from benchmarks.operator_time import operator_seconds

METRIC = {"name": "ops.ssm_ms_per_step", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "ops",
          "moves": "train_items_per_s_per_chip"}

OPERATORS = re.compile(r"^blk\d+_ssm_(in|scan|out)$")


def read(facts):
    seconds = operator_seconds(facts, OPERATORS)
    if seconds is None:
        return None
    return 1e3 * seconds / facts["traced_steps"]
