"""Model FLOP/s utilization: the forward + backward FLOPs one item needs
(the configuration's shape function under benchmarks/flops/) times
items per second, over chips times the bf16 peak."""

from benchmarks.stats import whole_step_rate

METRIC = {"name": "ops.mfu", "unit": "%", "better": "higher",
          "source": "host_clock", "layer": "ops",
          "moves": "train_items_per_s_per_chip"}


def read(facts):
    if "fences" not in facts or "peaks" not in facts:
        return None
    per_item = facts["flops"].train_flops_per_item(facts["config"],
                                                   facts["mix"])
    rate = whole_step_rate(facts["fences"])
    if not per_item or not rate:
        return None
    return 100.0 * per_item * rate / (
        facts["chips"] * facts["peaks"]["bf16_flops_per_s"])
