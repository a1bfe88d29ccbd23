"""Share of the window the train loop spent waiting for its next batch
(DevicePrefetcher's residual wait)."""

METRIC = {"name": "runtime.input_stall_share", "unit": "%",
          "better": "lower", "source": "program_counter",
          "layer": "runtime", "moves": "train_items_per_s_per_chip"}


def read(facts):
    if "input_stall_s" not in facts:
        return None
    window = facts["fences"][-1][0] - facts["fences"][0][0]
    return 100.0 * facts["input_stall_s"] / window
