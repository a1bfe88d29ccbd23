"""Share of the window Python's collector held the host: what the
program's ``runtime.gc_s`` counter (seconds inside ``gc.callbacks``'
start and stop, every generation) gained inside the window's fence
intervals, over the intervals' seconds."""

from benchmarks.host_timeline import metric

METRIC = {"name": "runtime.gc_pause_share", "unit": "%", "better": "lower",
          "source": "program_span", "layer": "runtime",
          "moves": "train_items_per_s_per_chip"}


def read(facts):
    return metric(facts, METRIC["name"])
