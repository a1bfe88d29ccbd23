"""The gated short convolutions' share of their roofline: the least time
the chip needs for the operators' FLOPs and bytes a step (``kernel_work``'s
``short_conv`` under ``benchmarks/flops/``: two products forward and four
backward a layer, the activations moved once a pass, from shapes), over
the device time of the ``blk<i>_conv`` operators, all passes.  It is read
by operator and not by kernel name, so it measures the same work whether
plain XLA or a Pallas kernel does it, and the float32 convolution, the
gates and the recomputed forward lower it as the padding of a kernel
would.  Nothing on a program without such operators."""

import re

from benchmarks.operator_time import operator_seconds

METRIC = {"name": "kernels.short_conv_roofline", "unit": "%",
          "better": "higher", "source": "device_trace", "layer": "kernels",
          "moves": "train_items_per_s_per_chip"}

OPERATORS = re.compile(r"^blk\d+_conv$")
WORK = "short_conv"


def read(facts):
    work = getattr(facts.get("flops"), "kernel_work", None)
    if work is None or "peaks" not in facts:
        return None
    need = work(facts["config"], facts["mix"]).get(WORK)
    seconds = operator_seconds(facts, OPERATORS)
    if not need or not seconds:
        return None
    floor = max(need["flops"] / facts["peaks"]["bf16_flops_per_s"],
                need["bytes"] / facts["peaks"]["hbm_bytes_per_s"])
    return 100.0 * floor * facts["traced_steps"] / seconds
