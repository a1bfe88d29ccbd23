"""Share of the window the host spent inside the train step's calls: the
seconds of the program's ``ff:runtime.step`` spans that lie in the
window's fence intervals, over the intervals' seconds.  At 100 the host
sets the pace."""

from benchmarks.host_timeline import metric

METRIC = {"name": "runtime.host_dispatch_share", "unit": "%",
          "better": "lower", "source": "program_span", "layer": "runtime",
          "moves": "train_items_per_s_per_chip"}


def read(facts):
    return metric(facts, METRIC["name"])
