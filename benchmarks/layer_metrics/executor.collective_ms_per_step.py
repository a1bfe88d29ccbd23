"""Device milliseconds a step spends with a collective in flight,
hidden or not (mean of the devices)."""

METRIC = {"name": "executor.collective_ms_per_step", "unit": "ms",
          "better": "lower", "source": "device_trace", "layer": "executor",
          "moves": "train_items_per_s_per_chip"}


def read(facts):
    trace = facts.get("trace")
    if not trace or not facts.get("traced_steps"):
        return None
    return 1e3 * trace["collective_s"] / facts["traced_steps"]
