"""Pass 1 — sync-freedom / host-transfer lint.

Three views of the same invariant ("the per-step hot path makes zero
host round-trips"), because each catches what the others cannot:

* the **jaxpr** of the traced step sees host callbacks staged into the
  program (``debug_callback`` / ``debug_print`` / ``pure_callback`` /
  ``io_callback``) before XLA rewrites them;
* the **compiled HLO** sees what actually lowered: callback
  custom-calls, ``infeed``/``outfeed``, host-transfer send/recv;
* the **source AST** of the fit hot path sees Python-side syncs the
  trace never contains (``device_get``, ``block_until_ready``,
  ``.item()``, implicit ``float()`` concretization of device values) —
  flagged unless the statement carries an approved boundary marker
  ``# sync-ok: <reason>`` on its own lines or the line above.
"""

from __future__ import annotations

import ast
import re
from typing import List, Optional, Sequence

from flexflow_tpu.verify.findings import Finding

# jaxpr primitives that stage a host round-trip into the step
JAXPR_HOST_PRIMS = ("debug_callback", "debug_print", "pure_callback",
                    "io_callback", "infeed", "outfeed")

# HLO custom-call targets that are python/host callbacks
_HLO_CALLBACK = re.compile(
    r'custom_call_target="([^"]*(?:callback|host)[^"]*)"', re.I)

# Python calls that synchronize with the device unconditionally
_ALWAYS_SYNC = ("device_get", "block_until_ready", "item")

# float()/int()/bool() only syncs when its argument is a device value;
# config/shape conversions are host-side and must not be flagged
_DEVICE_VALUE = re.compile(r"loss|grad|param|logit|metric|sig\b")

_MARKER = re.compile(r"#\s*sync-ok\s*:?\s*(.*)")


def jaxpr_sync_findings(jaxpr, label: str = "step") -> List[Finding]:
    """Walk a (Closed)Jaxpr recursively for host-round-trip primitives."""
    out: List[Finding] = []
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr -> Jaxpr

    def walk(j):
        for eqn in j.eqns:
            name = eqn.primitive.name
            if any(p in name for p in JAXPR_HOST_PRIMS):
                out.append(Finding(
                    "sync", "jaxpr_host_prim", "error",
                    f"{label}:jaxpr:{name}",
                    f"traced step stages host primitive {name!r} — a "
                    f"per-step host round-trip"))
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None and hasattr(inner, "eqns"):
                        walk(inner)
                    elif hasattr(sub, "eqns"):
                        walk(sub)

    walk(jaxpr)
    return out


def hlo_sync_findings(hlo: str, label: str = "step") -> List[Finding]:
    """Scan compiled HLO text for host transfers the program would pay
    every step."""
    out: List[Finding] = []
    for m in _HLO_CALLBACK.finditer(hlo):
        out.append(Finding(
            "sync", "hlo_callback", "error",
            f"{label}:hlo:{m.group(1)}",
            f"compiled program calls host callback {m.group(1)!r}"))
    for op in ("infeed", "outfeed"):
        for _ in re.finditer(rf"(?<=[\s(]){op}\(", hlo):
            out.append(Finding(
                "sync", "hlo_" + op, "error", f"{label}:hlo:{op}",
                f"compiled program contains {op} — a host transfer in "
                f"the step"))
    for m in re.finditer(r"(?<=[\s(])(send|recv)\([^\n]*"
                         r"is_host_transfer=true", hlo):
        out.append(Finding(
            "sync", "hlo_host_transfer", "error",
            f"{label}:hlo:{m.group(1)}",
            f"compiled program {m.group(1)}s to the host every step"))
    return out


def _marked_ok(lines: Sequence[str], lineno: int,
               end_lineno: int) -> Optional[str]:
    """The ``# sync-ok: reason`` marker on any physical line of the
    enclosing statement or in the contiguous comment block above it;
    returns the reason, '' when the marker has none (itself a finding),
    None when unmarked."""
    hi = min(end_lineno, len(lines))
    for i in range(max(lineno - 1, 0), hi):   # the statement's own lines
        m = _MARKER.search(lines[i])
        if m:
            return m.group(1).strip()
    i = lineno - 2                            # comment block above
    while i >= 0 and lines[i].strip().startswith("#"):
        m = _MARKER.search(lines[i])
        if m:
            return m.group(1).strip()
        i -= 1
    return None


def _call_name(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def _touches_device_value(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        name = None
        if isinstance(sub, ast.Name):
            name = sub.id
        elif isinstance(sub, ast.Attribute):
            name = sub.attr
        if name and _DEVICE_VALUE.search(name):
            return True
    return False


def source_sync_findings(source: str, filename: str = "model.py",
                         funcs: Sequence[str] = ("fit", "_fit"),
                         ) -> List[Finding]:
    """AST pass over the per-step region: flag Python-side sync calls in
    the named functions unless bracketed by ``# sync-ok: reason``."""
    tree = ast.parse(source, filename=filename)
    lines = source.splitlines()
    out: List[Finding] = []

    def scan(fn: ast.FunctionDef):
        stmts = [n for n in ast.walk(fn) if isinstance(n, ast.stmt)]

        def enclosing(call):
            """Innermost statement containing the call — its span (plus
            the comment block above it) is where the marker may live."""
            best = None
            ce = call.end_lineno or call.lineno
            for st in stmts:
                se = st.end_lineno or st.lineno
                if st.lineno <= call.lineno and se >= ce:
                    if best is None or se - st.lineno <= \
                            (best.end_lineno or best.lineno) - best.lineno:
                        best = st
            return best or call

        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            syncs = False
            if name in _ALWAYS_SYNC:
                syncs = True
            elif name in ("float", "int", "bool") and node.args \
                    and _touches_device_value(node.args[0]):
                syncs = True
            if not syncs:
                continue
            stmt = enclosing(node)
            reason = _marked_ok(lines, stmt.lineno,
                                stmt.end_lineno or stmt.lineno)
            where = f"{filename}:{fn.name}:{name}"
            if reason is None:
                out.append(Finding(
                    "sync", name, "error", where,
                    f"{filename}:{node.lineno}: per-step region calls "
                    f"{name}() with no '# sync-ok: reason' marker — a "
                    f"Python-side device sync"))
            elif not reason:
                out.append(Finding(
                    "sync", name, "error", where,
                    f"{filename}:{node.lineno}: '# sync-ok' marker has "
                    f"no reason — every approved sync must say why"))
            else:
                out.append(Finding(
                    "sync", name, "info", where,
                    f"{filename}:{node.lineno}: approved sync ({reason})",
                    exempted=True, reason=reason))

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name in funcs:
            scan(node)
    return out
