"""The compiled step against its own roofline: the least time the chip
could take for the FLOPs and bytes XLA counts in the compiled step (the
larger of FLOPs over the bf16 peak and bytes over the HBM peak), over the
device-busy time of a step in the traced window.

XLA counts nothing for a custom call, so BENCHMARK.json lists this
metric only for one-chip cells whose routed Pallas kernels are a few
percent of the step (Inception: 3%, so the share reads that much low).
Where they are most of it (GPT-2 training: 57%) the count is of another
program than the one that ran; and whether an SPMD program's counts are
a chip's or the machine's is not settled (PERF.md section 7)."""

METRIC = {"name": "ops.step_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "ops",
          "moves": "train_items_per_s_per_chip"}


def read(facts):
    trace = facts.get("trace")
    if not trace or not facts.get("xla_flops") or "peaks" not in facts \
            or not facts.get("traced_steps"):
        return None
    floor = max(facts["xla_flops"] / facts["peaks"]["bf16_flops_per_s"],
                facts["xla_bytes"] / facts["peaks"]["hbm_bytes_per_s"])
    return 100.0 * floor * facts["traced_steps"] / trace["busy_s"]
