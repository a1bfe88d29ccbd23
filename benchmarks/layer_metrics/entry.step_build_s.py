"""Seconds of the train step's first call before the window opened:
the program's ``ff:entry.step_build`` span, which holds the step's
tracing, its lowering, the compilation or its fetch from the cache and
the first dispatch (``benchmarks/host_timeline.py`` prints its parts)."""

from benchmarks.host_timeline import metric

METRIC = {"name": "entry.step_build_s", "unit": "s", "better": "lower",
          "source": "program_span", "layer": "entry points",
          "moves": "setup_s"}


def read(facts):
    return metric(facts, METRIC["name"])
