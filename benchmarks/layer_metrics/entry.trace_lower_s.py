"""Seconds JAX spent tracing Python into jaxprs and lowering them to MLIR
before the window opened: the program's ``compile.trace_s`` +
``compile.lower_s`` counters (JAX's own monitoring events), read at the
instant the window opened."""

from benchmarks.program_trace import program_facts

METRIC = {"name": "entry.trace_lower_s", "unit": "s", "better": "lower",
          "source": "program_counter", "layer": "entry points",
          "moves": "setup_s"}


def read(facts):
    prog = program_facts(facts)
    if not prog:
        return None
    c = prog["compile_before_open_s"]
    return c["compile.trace_s"] + c["compile.lower_s"]
