"""The windowed flash-attention kernels' share of their roofline: the
least time the chip could take for the FLOPs and bytes the sliding layers'
attention needs a step (``kernel_work``'s ``ff_flash_win_`` under
``benchmarks/flops/``: every query against the keys of its window, from
shapes), over the device time of the ``ff_flash_win_*`` custom calls of a
step.  The work is the window's, so pieces of a tile the mask leaves
nothing of, keys repeated for their group and the recomputed forward
lower it.  Nothing on a program without such kernels."""

from benchmarks.trace_reduce import kernel_seconds

METRIC = {"name": "kernels.window_attn_roofline", "unit": "%",
          "better": "higher", "source": "device_trace", "layer": "kernels",
          "moves": "train_items_per_s_per_chip"}

PREFIX = "ff_flash_win_"


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
