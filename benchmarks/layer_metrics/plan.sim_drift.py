"""Measured step seconds over the step seconds the plan's artifact
predicted (``__predicted__.best_time_s``, the simulator's price of the
plan the search returned).  Reported, not trusted."""

from benchmarks.stats import interval_step_seconds, percentile

METRIC = {"name": "plan.sim_drift", "unit": "ratio", "better": "lower",
          "source": "program_span", "layer": "plan",
          "moves": "train_items_per_s_per_chip"}


def read(facts):
    predicted = (facts.get("plan_predicted") or {}).get("best_time_s")
    if not predicted or "fences" not in facts:
        return None
    return percentile(interval_step_seconds(
        facts["fences"], facts["items_per_step"]), 50) / predicted
