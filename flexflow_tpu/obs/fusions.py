"""Per-fusion residual account (round 13) — ``report fusions``.

The roofline profile (utils/hlo_profile.roofline_report, committed under
examples/profiles/) ends at a single number: the step runs at
``of_ceiling`` of its floor, leaving ``seconds_per_step -
step_floor_seconds`` of *compute residual* the class split only coarsely
attributes.  This module prices each profiled fusion against the
:class:`~flexflow_tpu.sim.cost_model.TpuChipPerf` roofline and produces
a ranked account of that residual with the same accounting contract as
``obs.budget.build_step_budget``: row allocations are clamped to the
remaining residual, the remainder is an explicit ``unattributed`` bucket,
and rows + unattributed sum to the residual EXACTLY — an account, not an
estimate dump.  Raw (pre-clamp) excesses are kept per row for honesty.

Per-row floors, by fusion class:

* ``vpu`` / ``raw``-with-root — HBM byte floor from the root line's
  output shapes (the same ``dtype[dims]`` line parser as
  utils/hlo_audit.parse_collectives; layout annotations use parens, so
  the bracket regex is safe), with the input volume estimated from the
  root opcode (an ``add`` reads 2x its output, a ``select`` ~2.25x, a
  ``tuple`` root is priced at output volume — a stated lower bound).
* ``mxu`` — byte floors cannot see matrix-unit inefficiency, so the
  floor is ``measured * mxu_eff_during_matmul`` (the profile's own
  flops/(peak * mxu_ms)): what the row would take at 100% MXU.
* a row whose root line carries no shapes (``select_and_scatter``, the
  max-pool backward's unfusable scatter) is priced at its measured time
  and says so (``unpriced``): the Pallas kernel that once priced it
  beat the scatter alone and lost both its cells end to end (PERF.md
  section 6, PR 30).

Every row carries a machine-applied verdict — ``fusable`` (elementwise
excess XLA could fold into a producer/consumer), ``pallas_worthy``
(unvectorized code above its byte floor), or ``irreducible``
(at its floor, or MXU-internal utilization no byte rewrite recovers).

jax-free on purpose: ``make fusion-smoke`` runs against the committed
profile in the native-only ``make check`` path.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

# the one dtype-size table shared with the collective auditor
from flexflow_tpu.utils.hlo_audit import _DT

_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OPCODE = re.compile(r"([a-z][a-z0-9_\-]*)\(")

# input volume as a multiple of output volume, by root opcode.  Stated
# estimates: a 2-operand elementwise op reads 2x what it writes; select
# reads two branches + a pred plane (~0.25x at 1 byte vs bf16/f32);
# roots whose operand set the line does not reveal (tuple, reduce,
# convert chains) are priced at output volume — a LOWER bound, so their
# excess is an upper bound and the verdict stays conservative.
_IN_MULT = {"add": 2.0, "subtract": 2.0, "multiply": 2.0, "divide": 2.0,
            "maximum": 2.0, "minimum": 2.0, "select": 2.25,
            "select-n": 2.25, "select_n": 2.25}

# balanced-tree gradient fanout (ops/fanout.py): an n-way branch sum as
# one (n+1)-operand fusion moves (n+1) units vs the add_any chain's
# 3(n-1); at the inception blocks' n=4 that is 5/9 of the traffic
_FANOUT_TRAFFIC_RATIO = 5.0 / 9.0

SCHEMA = "fusion_account_v1"


def _root_bytes(root: str) -> Optional[Dict[str, float]]:
    """Output bytes + estimated input bytes of a profile row's root HLO
    line, or None when the line carries no parseable shapes."""
    op = None
    pos = len(root)
    m = _OPCODE.search(root.split("=", 1)[-1])
    if m:
        op = m.group(1)
        pos = root.index(m.group(0), root.find("=") + 1)
    out = 0
    for sm in _SHAPE.finditer(root[:pos]):
        dt, dims = sm.group(1), sm.group(2)
        if dt not in _DT:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out += n * _DT[dt]
    if out <= 0:
        return None
    mult = _IN_MULT.get(op or "", 1.0)
    return {"out_bytes": float(out), "in_bytes": float(out) * mult,
            "opcode": op or "", "lower_bound": op not in _IN_MULT}


def _price_row(row: dict, mxu_eff: float, hbm_bw: float) -> dict:
    """floor_ms + floor_source (+ kernel/rewrite annotation) for one
    profiled fusion row ({name, ms, class, root})."""
    name, ms = row["name"], float(row["ms"])
    cls, root = row.get("class", ""), row.get("root", "") or ""
    out = {"name": name, "class": cls, "measured_ms": ms}
    if cls == "mxu":
        out["floor_ms"] = ms * mxu_eff
        out["floor_source"] = "mxu_flops"
        out["note"] = (f"at {mxu_eff:.0%} MXU during matmul; excess is "
                       f"matrix-unit utilization, not HBM traffic")
        return out
    priced = _root_bytes(root)
    if priced is None:
        # no shapes on the root line: price at measured (excess 0) and
        # say so rather than invent a floor
        out["floor_ms"] = ms
        out["floor_source"] = "unpriced"
        out["note"] = "root line carries no parseable shapes"
        return out
    bw_ms = (priced["in_bytes"] + priced["out_bytes"]) / hbm_bw * 1e3
    out["floor_ms"] = min(bw_ms, ms)
    out["floor_source"] = ("root_bytes_lower_bound"
                           if priced["lower_bound"] else "root_bytes")
    out["excess_bytes"] = round(max(0.0, ms - out["floor_ms"])
                                / 1e3 * hbm_bw)
    # only when the root DEFINES the add_any (the fusion IS the
    # accumulation chain), not when it merely reads one as an operand
    if root.lstrip().startswith("%add_any"):
        out["rewrite"] = "grad_fanout"
        out["predicted_win_ms"] = round(
            max(0.0, ms - out["floor_ms"]) * (1 - _FANOUT_TRAFFIC_RATIO),
            3)
        out["note"] = ("branch-gradient add_any chain; grad_fanout tree "
                       f"moves {_FANOUT_TRAFFIC_RATIO:.2f}x the bytes")
    return out


def _verdict(row: dict) -> str:
    tol = max(0.05, 0.05 * row["measured_ms"])
    if row["measured_ms"] - row["floor_ms"] <= tol:
        return "irreducible"
    if row["class"] == "mxu":
        return "irreducible"
    if row["class"] == "raw":
        return "pallas_worthy"
    return "fusable"


def fusion_account(profile: dict, perf=None, top_n: int = 10) -> dict:
    """The ranked residual account for one roofline profile dict
    (examples/profiles/*_roofline.json schema).  Rows are the ``top_n``
    largest pre-clamp excesses; allocation is greedy in that order and
    clamped to the remaining residual (clamped rows listed), and
    ``rows[*].excess_ms + unattributed_ms == residual_ms`` exactly."""
    if perf is None:
        from flexflow_tpu.sim.cost_model import TpuChipPerf, chip_perf

        # apps/profile.py records the chip it traced; the committed
        # profiles that predate the field were taken on a v5e
        kind = profile.get("device_kind")
        perf = chip_perf(kind) if kind else TpuChipPerf()
    wall_ms = float(profile["seconds_per_step"]) * 1e3
    floor_ms = float(profile["step_floor_seconds"]) * 1e3
    residual_ms = max(0.0, wall_ms - floor_ms)
    mxu_eff = float(profile.get("mxu_eff_during_matmul") or 1.0)
    priced = [_price_row(r, mxu_eff, perf.hbm_bandwidth)
              for r in profile.get("top_ops", [])]
    for p in priced:
        p["excess_ms_raw"] = round(
            max(0.0, p["measured_ms"] - p["floor_ms"]), 3)
        p["floor_ms"] = round(p["floor_ms"], 3)
        p["verdict"] = _verdict(p)
    priced.sort(key=lambda p: p["excess_ms_raw"], reverse=True)
    rows, clamped = priced[:top_n], []
    remaining = residual_ms
    for p in rows:
        alloc = min(p["excess_ms_raw"], remaining)
        if alloc < p["excess_ms_raw"] - 1e-9:
            clamped.append(p["name"])
        p["excess_ms"] = alloc
        p["share_of_residual"] = (alloc / residual_ms
                                  if residual_ms else 0.0)
        remaining -= alloc
    attributed = sum(p["excess_ms"] for p in rows)
    return {"schema": SCHEMA, "model": profile.get("model", ""),
            "bound": profile.get("bound", ""),
            "wall_ms": wall_ms, "floor_ms": floor_ms,
            "residual_ms": residual_ms, "mxu_eff": mxu_eff,
            "rows": rows, "attributed_ms": attributed,
            "unattributed_ms": remaining, "clamped": clamped,
            "top3_frac": (sum(p["excess_ms"] for p in rows[:3])
                          / residual_ms if residual_ms else 0.0)}


def check_account(account: dict, tol_frac: float = 0.01) -> List[str]:
    """The fusion-smoke invariants: rows + unattributed sum to the
    residual within ``tol_frac``, and every row is verdicted (no
    ``unknown``).  Returns problem strings; [] means the account holds."""
    problems = []
    total = (sum(r["excess_ms"] for r in account["rows"])
             + account["unattributed_ms"])
    ref = max(account["residual_ms"], 1e-9)
    if abs(total - account["residual_ms"]) > tol_frac * ref:
        problems.append(
            f"rows+unattributed = {total:.3f} ms != residual "
            f"{account['residual_ms']:.3f} ms")
    for r in account["rows"]:
        if r.get("verdict") not in ("fusable", "pallas_worthy",
                                    "irreducible"):
            problems.append(f"row {r['name']} verdict "
                            f"{r.get('verdict')!r} is not a verdict")
    return problems


def residual_top_frac(profile: dict, k: int = 3) -> float:
    """Share of the compute residual held by the account's top-``k``
    rows (bench.py's ``residual_top_frac`` metric field)."""
    acct = fusion_account(profile)
    ref = acct["residual_ms"]
    return (sum(r["excess_ms"] for r in acct["rows"][:k]) / ref
            if ref else 0.0)


def render_account(account: dict) -> str:
    """Fixed-width text table (``report fusions`` default output)."""
    lines = [
        f"fusion residual account — {account['model'] or '?'} "
        f"({account['bound'] or '?'}-bound): wall {account['wall_ms']:.2f}"
        f" ms, floor {account['floor_ms']:.2f} ms, residual "
        f"{account['residual_ms']:.2f} ms",
        f"{'fusion':<28}{'class':<6}{'meas':>8}{'floor':>8}"
        f"{'excess':>8}{'share':>7}  verdict"]
    for r in account["rows"]:
        extra = ""
        if r.get("kernel"):
            extra = (f"  [{r['kernel']} "
                     f"-{r.get('predicted_win_ms', 0):.2f} ms]")
        elif r.get("rewrite"):
            extra = (f"  [{r['rewrite']} "
                     f"-{r.get('predicted_win_ms', 0):.2f} ms]")
        clamp = "*" if r["name"] in account["clamped"] else " "
        lines.append(
            f"{r['name']:<28}{r['class']:<6}{r['measured_ms']:>8.3f}"
            f"{r['floor_ms']:>8.3f}{r['excess_ms']:>7.3f}{clamp}"
            f"{r['share_of_residual']:>7.1%}  {r['verdict']}{extra}")
    lines.append(
        f"{'unattributed (beyond top rows)':<42}"
        f"{account['unattributed_ms']:>8.3f}"
        f"{account['unattributed_ms'] / account['residual_ms']:>8.1%}"
        if account["residual_ms"] else "unattributed: 0")
    if account["clamped"]:
        lines.append(f"  * clamped to remaining residual: "
                     f"{', '.join(account['clamped'])}")
    return "\n".join(lines)
