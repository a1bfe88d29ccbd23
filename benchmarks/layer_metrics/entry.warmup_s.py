"""Seconds from the first call of the step to the instant the window
opens: the program's load or compilation and the warm-up steps."""

METRIC = {"name": "entry.warmup_s", "unit": "s", "better": "lower",
          "source": "host_clock", "layer": "entry points",
          "moves": "setup_s"}


def read(facts):
    return facts.get("phases", {}).get("warmup")
