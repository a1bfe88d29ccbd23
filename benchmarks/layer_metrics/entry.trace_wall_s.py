"""Wall seconds in which JAX traced Python into jaxprs or lowered them to
MLIR before the window opened, no nested level twice: the program's
``compile.trace_wall_s`` + ``compile.lower_wall_s`` counters (the union
of the intervals of JAX's own duration events, where
``entry.trace_lower_s`` sums them), read at the instant the window
opened."""

from benchmarks.host_timeline import metric

METRIC = {"name": "entry.trace_wall_s", "unit": "s", "better": "lower",
          "source": "program_counter", "layer": "entry points",
          "moves": "setup_s"}


def read(facts):
    return metric(facts, METRIC["name"])
