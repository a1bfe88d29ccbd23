"""Median over the window's fence intervals of the interval's seconds
per train step (bench:train_step spans between two bench:fences)."""

from benchmarks.stats import interval_step_seconds, percentile

METRIC = {"name": "runtime.step_ms_p50", "unit": "ms", "better": "lower",
          "source": "program_span", "layer": "runtime",
          "moves": "train_items_per_s_per_chip"}


def read(facts):
    if "fences" not in facts:
        return None
    p50 = percentile(interval_step_seconds(facts["fences"],
                                           facts["items_per_step"]), 50)
    return None if p50 is None else 1e3 * p50
