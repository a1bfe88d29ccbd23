"""Share of the traced window in which no operation ran on the device
(1 - busy union over the window, mean of the chips used)."""

METRIC = {"name": "device.idle_share.train", "unit": "%", "better": "lower",
          "source": "device_trace", "layer": "device",
          "moves": "train_items_per_s_per_chip"}


def read(facts):
    trace = facts.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
