"""Share of the traced window in which a collective ran on a device while
no other operation did (mean of the devices)."""

METRIC = {"name": "executor.exposed_collective_share", "unit": "%",
          "better": "lower", "source": "device_trace", "layer": "executor",
          "moves": "train_items_per_s_per_chip"}


def read(facts):
    trace = facts.get("trace")
    if not trace:
        return None
    return 100.0 * trace["exposed_collective_s"] / trace["window_s"]
