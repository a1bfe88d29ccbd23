"""Seconds the program's entry points spent making the train state and
its plans before the window opened: self time of the ``ff:entry.*`` spans
``abstract_state``, ``init`` (under the driver's ``jax.jit`` the Python
tracing of the draws), ``opt_state``, ``graph_plan`` and ``regrid_plan``
(``obs.snapshot()``; self time, because they nest)."""

from benchmarks.program_trace import program_facts

METRIC = {"name": "entry.state_init_s", "unit": "s", "better": "lower",
          "source": "program_span", "layer": "entry points",
          "moves": "setup_s"}


def read(facts):
    prog = program_facts(facts)
    if not prog:
        return None
    return sum(prog["entry_before_open_s"].values())
