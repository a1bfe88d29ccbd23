"""Device milliseconds a step spends in the repo's Pallas kernels: the
custom calls named ``ff_*`` in the traced window, per step."""

from benchmarks.trace_reduce import kernel_seconds

METRIC = {"name": "kernels.pallas_ms_per_step", "unit": "ms",
          "better": "lower", "source": "device_trace", "layer": "kernels",
          "moves": "train_items_per_s_per_chip"}


def read(facts):
    trace = facts.get("trace")
    if not trace or not facts.get("traced_steps"):
        return None
    return 1e3 * kernel_seconds(trace, "ff_") / facts["traced_steps"]
