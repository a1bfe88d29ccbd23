"""MFU-waterfall smoke — the ``make budget-smoke`` entry point for the
step-budget + metrics observability layer.

One tiny CNN trains on the local backend with sampled op timing
(``op_time_every``) and live metrics export (``metrics_path``), then the
assertions:

  1. the obs stream carries a ``step_budget`` record satisfying the
     bucket invariant (every bucket non-negative, buckets sum <= the
     measured step wall time — obs/budget.py ``check_budget``);
  2. ``report budget <obs_dir>`` renders an MFU waterfall from the
     fresh obs dir;
  3. the Prometheus textfile parses and carries finite throughput
     gauges — and ``mfu`` exactly when the backend is a TPU (a CPU has
     no peak to be a fraction of) — and the JSON snapshot exists;
  4. the fit trace's Perfetto counter lanes (imgs/s, HBM bytes, MFU on a
     TPU) pass ``validate_trace``.

Everything runs on CPU in seconds; assertion failures exit non-zero.

    JAX_PLATFORMS=cpu python -m flexflow_tpu.apps.budget_smoke
"""

from __future__ import annotations

import math
import os
import sys
import tempfile

ITERS = 6


def _build(cfg, machine):
    from flexflow_tpu.model import FFModel

    ff = FFModel(cfg, machine)
    img = ff.create_input((cfg.batch_size, 16, 16, 3), name="image")
    t = ff.conv2d("conv1", img, 8, 3, 3, 1, 1, 1, 1, relu=True)
    t = ff.flat("flat", t)
    t = ff.linear("fc", t, 8, relu=False)
    ff.softmax("softmax", t)
    return ff


def main() -> int:
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.data import synthetic_batches
    from flexflow_tpu.machine import MachineModel
    from flexflow_tpu.obs import read_run
    from flexflow_tpu.obs.budget import check_budget
    from flexflow_tpu.obs.metrics import read_textfile
    from flexflow_tpu.obs.trace import (chrome_trace, fit_trace_events,
                                        validate_trace)

    tmp = tempfile.mkdtemp(prefix="budget-smoke-")
    obs_dir = os.path.join(tmp, "obs")
    metrics_path = os.path.join(tmp, "metrics.prom")
    cfg = FFConfig(batch_size=8, input_height=16, input_width=16,
                   num_iterations=ITERS, print_freq=3, num_classes=8,
                   obs_dir=obs_dir, run_id="budget-smoke",
                   op_time_every=2, metrics_path=metrics_path)
    machine = MachineModel()
    ff = _build(cfg, machine)
    data = synthetic_batches(machine, cfg.batch_size, 16, 16,
                             num_classes=8, mode="random", seed=0)
    out = ff.fit(data, log=lambda *a: print(*a, file=sys.stderr))

    evs = list(read_run(out["obs_path"]))
    budgets = [e for e in evs if e.get("kind") == "step_budget"]
    assert len(budgets) == 1, f"expected 1 step_budget, got {budgets}"
    violations = check_budget(budgets[0])
    assert not violations, violations
    buckets = budgets[0]["buckets"]
    assert sum(buckets.values()) <= budgets[0]["step_wall_s"] * (1 + 1e-6)

    # the waterfall renders from the FRESH obs dir via the CLI
    from flexflow_tpu.apps import report

    lines = []
    rc = report.main(["budget", obs_dir], log=lines.append)
    text = "\n".join(str(l) for l in lines)
    assert rc == 0, f"report budget rc={rc}:\n{text}"
    assert "MFU waterfall" in text and "remove bucket" in text, text
    print(text, file=sys.stderr)

    vals = read_textfile(metrics_path)
    for key in ("throughput_items_per_sec", "images_per_sec",
                "steps_total"):
        assert key in vals and math.isfinite(vals[key]), (key, vals)
    on_tpu = machine.devices[0].platform == "tpu"
    assert ("mfu" in vals) == on_tpu, vals
    assert vals["steps_total"] == ITERS, vals
    assert os.path.exists(metrics_path + ".json")

    trace = chrome_trace(fit_trace_events(evs))
    errors = validate_trace(trace)
    assert not errors, errors
    counters = [e for e in trace["traceEvents"] if e.get("ph") == "C"]
    names = {e["name"] for e in counters}
    assert "imgs/s" in names and ("MFU" in names) == on_tpu, names

    print(f"budget-smoke OK: step {budgets[0]['step_wall_s'] * 1e3:.2f} "
          f"ms decomposed into {len(buckets)} buckets "
          f"(residual {buckets['residual'] * 1e3:.2f} ms), "
          f"mfu gauge {vals.get('mfu', 'not published off the TPU')}, "
          f"{len(counters)} counter samples across {sorted(names)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
