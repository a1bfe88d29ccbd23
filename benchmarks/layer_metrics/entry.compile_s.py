"""Seconds JAX spent in backend compilation before the window opened, a
persistent-cache hit counting its retrieval (JAX monitoring events)."""

METRIC = {"name": "entry.compile_s", "unit": "s", "better": "lower",
          "source": "program_counter", "layer": "entry points",
          "moves": "setup_s"}


def read(facts):
    return facts.get("compile_at_open", {}).get("compile_s")
