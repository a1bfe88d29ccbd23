"""The flash-attention kernels' share of their roofline: the least time
the chip could take for the attention FLOPs and bytes a step needs (from
shapes, ``benchmarks/flops/``), over the device time of the
``ff_flash_*`` custom calls of a step."""

from benchmarks.trace_reduce import kernel_seconds

METRIC = {"name": "kernels.flash_attn_roofline", "unit": "%",
          "better": "higher", "source": "device_trace", "layer": "kernels",
          "moves": "train_items_per_s_per_chip"}

PREFIX = "ff_flash_"


def read(facts):
    trace = facts.get("trace")
    work = getattr(facts.get("flops"), "kernel_work", None)
    if not trace or work is None or "peaks" not in facts \
            or not facts.get("traced_steps"):
        return None
    need = work(facts["config"], facts["mix"]).get(PREFIX)
    seconds = kernel_seconds(trace, PREFIX) / facts["traced_steps"]
    if not need or not seconds:
        return None
    floor = max(need["flops"] / facts["peaks"]["bf16_flops_per_s"],
                need["bytes"] / facts["peaks"]["hbm_bytes_per_s"])
    return 100.0 * floor / seconds
