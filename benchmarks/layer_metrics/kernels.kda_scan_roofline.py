"""The KDA scan's share of its roofline: the least time the chip needs
for the scan's FLOPs and bytes a step (``kernel_work``'s ``kda_scan``
under ``benchmarks/flops/``: the chunked form's products at the
published chunk and what the scan must read and write, from shapes), over
the device time of the ``blk<i>_kda_scan`` operators, all passes.  It is
read by operator and not by kernel name, so it measures the same work
whether plain XLA or a Pallas kernel does it, and the sub-chunks'
factors, the states written between chunks and the recomputed forward
lower it as the padding of a kernel would.  Nothing on a program without
such operators."""

import re

from benchmarks.operator_time import operator_seconds

METRIC = {"name": "kernels.kda_scan_roofline", "unit": "%",
          "better": "higher", "source": "device_trace", "layer": "kernels",
          "moves": "train_items_per_s_per_chip"}

OPERATORS = re.compile(r"^blk\d+_kda_scan$")
WORK = "kda_scan"


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
