"""Compiled-step HLO profiling: per-op device times from a jax.profiler
trace, classified against the compiled HLO (MXU conv/dot fusions vs
elementwise/VPU), plus the roofline ceiling analysis.

This is the deep end of the reference's ``profiling`` flag (per-task
cudaEvent ms, conv_2d.cu:514-545): under XLA the step is one fused program,
so honest per-op attribution must come from the device trace of the
compiled executable, not from isolated op timings (utils/profiling.py's
OpProfiler remains the attribution *estimate*; this module measures the
real thing).

Typical use (see apps/profile.py for the CLI):

    compiled = model.compile_train_step(*batch)
    with jax.profiler.trace(logdir):
        ... run steps ...
    times = device_op_times(logdir)          # {hlo op name: ms}
    cls = classify_ops(compiled.as_text(), times)
    report = roofline_report(compiled, seconds_per_step, perf, cls)
"""

from __future__ import annotations

import glob
import gzip
import json
import re
from collections import defaultdict
from typing import Dict, Optional


def device_op_times(logdir: str, steps: int = 1) -> Dict[str, float]:
    """Aggregate device-side op durations (ms, divided by ``steps``) from
    the newest perfetto trace under ``logdir``.  Module-level pseudo events
    (bare numerals, jit_* wrappers) are dropped."""
    files = sorted(glob.glob(f"{logdir}/**/*.trace.json.gz", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .trace.json.gz under {logdir}")
    with gzip.open(files[-1], "rt") as fh:
        tr = json.load(fh)
    pidname = {}
    for e in tr.get("traceEvents", []):
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pidname[e["pid"]] = e["args"].get("name", "")
    devpids = {p for p, n in pidname.items()
               if "TPU" in n or "GPU" in n}
    # under SPMD every chip runs the same program: average over device
    # pids so per-op ms stays per-chip on multi-chip hosts (summing would
    # inflate class totals num_devices-fold)
    agg: Dict[str, float] = defaultdict(float)
    for e in tr.get("traceEvents", []):
        if e.get("ph") != "X" or e.get("pid") not in devpids:
            continue
        name = e.get("name", "")
        if name.startswith("jit_") or re.fullmatch(r"\d+", name):
            continue
        agg[name] += (e.get("dur", 0) / 1e3 / max(steps, 1)
                      / max(len(devpids), 1))
    return dict(agg)


class HloIndex:
    """Fusion name -> called computation body, from ``compiled.as_text()``."""

    def __init__(self, hlo_text: str):
        self.lines = hlo_text.splitlines()
        self.calls: Dict[str, str] = {}
        for m in re.finditer(
                r'^\s*%?([\w.\-]+) = [^\n]*fusion\([^\n]*calls=%?([\w.\-]+)',
                hlo_text, re.M):
            self.calls[m.group(1)] = m.group(2)
        self.comp_start: Dict[str, int] = {}
        for j, l in enumerate(self.lines):
            m = re.match(r'^%?([\w.\-]+) \([^)]*\) -> ', l)
            if m:
                self.comp_start[m.group(1)] = j

    def body(self, op_name: str):
        comp = self.calls.get(op_name)
        if comp is None or comp not in self.comp_start:
            return None
        out = []
        for l in self.lines[self.comp_start[comp] + 1:]:
            if l.strip() == "}":
                break
            out.append(l)
        return out

    def classify(self, op_name: str) -> str:
        """'mxu' when the op's fusion body contains a convolution/dot (the
        MXU work rides there after fusion), 'raw' for unfusable HLO ops
        (select-and-scatter, bare converts/copies), else 'vpu'."""
        body = self.body(op_name)
        if body is None:
            if "convolution" in op_name or "dot" in op_name:
                return "mxu"
            return "raw"
        for l in body:
            if "convolution(" in l or " dot(" in l:
                return "mxu"
        return "vpu"


def classify_ops(hlo_text: str, times: Dict[str, float]):
    """[(ms, class, name, root-line)] sorted by time desc, plus per-class
    totals."""
    idx = HloIndex(hlo_text)
    rows = []
    totals: Dict[str, float] = defaultdict(float)
    for name, ms in sorted(times.items(), key=lambda kv: -kv[1]):
        c = idx.classify(name)
        totals[c] += ms
        root = ""
        body = idx.body(name)
        if body:
            for l in body:
                if l.strip().startswith("ROOT"):
                    root = l.strip()[5:]
                    break
        rows.append((ms, c, name, root))
    return rows, dict(totals)


def roofline_report(compiled, seconds_per_step: float, perf,
                    class_totals: Optional[Dict[str, float]] = None,
                    n_devices: int = 1) -> Dict:
    """Roofline ceiling analysis of the compiled step: arithmetic
    intensity vs the chip balance point, the HBM-bound step-time floor,
    and the MFU ceiling that floor implies.  ``mfu_ceiling`` is the honest
    upper bound for THIS compiled program on this chip — raising it
    requires removing bytes, not scheduling.  ``perf`` is the peaks of
    the chip the step was timed on (``sim.cost_model.chip_perf``)."""
    from flexflow_tpu.utils.profiling import compiled_roofline

    # single source for flops/bytes/utilizations (incl. the GLOBAL-flops-
    # under-SPMD convention documented there)
    rl = compiled_roofline(compiled, seconds_per_step, perf, n_devices)
    flops, bytes_ = rl["flops"], rl["bytes_accessed"]
    peak = perf.peak_flops * max(n_devices, 1)
    hbm = perf.hbm_bandwidth * max(n_devices, 1)
    intensity = flops / bytes_ if bytes_ else float("inf")
    balance = peak / hbm
    floor_s = max(flops / peak, bytes_ / hbm)
    out = {
        "seconds_per_step": seconds_per_step,
        "flops_per_step": flops,
        "bytes_per_step": bytes_,
        "arithmetic_intensity_flop_per_byte": intensity,
        "chip_balance_flop_per_byte": balance,
        "bound": "hbm" if intensity < balance else "mxu",
        "step_floor_seconds": floor_s,
        "mfu": rl.get("mxu_utilization"),
        "mfu_ceiling": flops / floor_s / peak if floor_s else None,
        "hbm_utilization": rl.get("hbm_utilization"),
        "of_ceiling": floor_s / seconds_per_step if seconds_per_step else None,
    }
    if class_totals:
        out["class_ms"] = {k: round(v, 3)
                           for k, v in sorted(class_totals.items())}
        mxu_ms = class_totals.get("mxu", 0.0)
        if mxu_ms:
            out["mxu_eff_during_matmul"] = flops / (mxu_ms / 1e3) / peak
    return out
