"""Compiled-HLO collective audit: executor-grounded communication
accounting for the strategy search (round 5, VERDICT r4 #1).

The reference's simulator was grounded on both axes: per-op times were
measured on the device (ref:scripts/cnn.h:204-447) and its comm model was
the same rectangle-intersection physics its executor (Legion) performed
(ref:scripts/simulator.cc:886-959).  This repo measures op costs
(protocol v3), but its comm model prices what GSPMD *should* lower — and
round 4's audit proved GSPMD sometimes lowers something else entirely
(the transformer_2x4 falsification: simulated 1.64x win, compiled program
moved ~8x MORE cross-tier bytes than DP).  This module makes the compiled
program itself the arbiter: lower the candidate plan on a virtual mesh,
parse the optimized HLO, and count the collective bytes that cross the
ICI-group (DCN) boundary.

Two entry points:

* :func:`audit_in_process` — requires ``len(jax.devices()) >= devices``
  (tests run it on the virtual CPU mesh via conftest's machine8).
* :func:`audit_subprocess` — spawns a fresh CPU process with
  ``--xla_force_host_platform_device_count=<devices>`` so the audit runs
  from ANY parent environment (including a parent that holds the one
  chip: the child is pinned to the CPU platform and never asks for it).
  This is what ``apps/search.py``'s accept path calls.

The byte counter itself (:func:`collective_bytes`) is the round-4 test
mechanism (tests/test_two_tier.py) promoted to library code; the static
verifier (flexflow_tpu/verify/, round 11) consumes the structured form
(:func:`collective_summary`) and prices it with the simulator's
calibrated ring formulas (:func:`sim.collectives.priced_collectives`),
upgrading :func:`audit_consistent`'s byte heuristic to predicted seconds
(:func:`audit_consistent_time`).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from typing import List, Optional, Tuple, Union

import numpy as np

_DT = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
       "u8": 1, "pred": 1, "f64": 8, "s64": 8}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "all-reduce-start", "all-gather-start",
                "reduce-scatter-start", "all-to-all-start",
                "collective-permute-start")

# op-position sighting of ANY collective mnemonic (incl. the -done halves
# of async pairs, which carry no replica_groups and must not be counted
# again) — the strict-parse net under the main shape-anchored regex
_SIGHT = re.compile(
    r"(?<=[\s(])(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start|-done)?\(")


class AuditParseError(ValueError):
    """A line that names a collective was not parsed by the counter —
    counting gaps fail loudly instead of silently under-counting
    (round 11 corpus hardening)."""


def parse_collectives(hlo: str, group_size: int,
                      devices: Optional[int] = None) -> List[dict]:
    """Structured records for every collective in optimized HLO text::

        {"op": str,          # HLO mnemonic (incl. a -start suffix)
         "bytes": float,     # buffer moved (see volume convention below)
         "cross": bool,      # any group/pair spans ICI groups
         "groups": [[ids]],  # replica groups (or permute pairs) as
                             #  device-id lists; [] when unknowable
         "async": bool}      # -start half of an async pair

    Volume convention: a sync collective's shape IS the moved buffer and
    tuple shapes (variadic operands) sum; an async ``-start`` tuple is
    ``(operands..., results..., scratch)`` describing ONE transfer, so it
    contributes its LARGEST element (the in-flight buffer), not the sum —
    the round-11 corpus showed the old sum double-counted every async
    pair.  ``-done`` halves carry no groups and are skipped (their
    ``-start`` already counted).  A collective mnemonic on a line the
    shape-anchored regex cannot parse raises :class:`AuditParseError`
    (except an unterminated final line, which parses fine).  With no
    ``replica_groups`` in the line, the group is all ``devices`` when
    given (flattened single-group form), else unknown (``groups=[]``,
    cross=False).
    """
    out: List[dict] = []
    consumed = set()
    for m in re.finditer(
            r"= ?((?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)) ([a-z\-]+)\(",
            hlo):
        shape_s, op = m.group(1), m.group(2)
        if op not in _COLLECTIVES:
            continue
        # a collective on an unterminated final line must not raise
        bol = hlo.rfind("\n", 0, m.start()) + 1
        eol = hlo.find("\n", m.start())
        consumed.add(bol)
        line = hlo[m.start():eol if eol != -1 else len(hlo)]
        elems = []
        for sm in re.finditer(r"([a-z0-9]+)\[([0-9,]*)\]", shape_s):
            dt, dims = sm.group(1), sm.group(2)
            if dt not in _DT:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            elems.append(n * _DT[dt])
        is_async = op.endswith("-start")
        nbytes = (max(elems) if is_async else sum(elems)) if elems else 0
        groups: List[List[int]] = []
        is_cross = False
        rg = re.search(r"replica_groups=\{(\{[0-9,\}\{]*\})\}", line)
        if rg:
            for grp in re.findall(r"\{([0-9,]+)\}", rg.group(1)):
                ids = [int(x) for x in grp.split(",")]
                groups.append(ids)
                if len({i // group_size for i in ids}) > 1:
                    is_cross = True
        ri = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\]"
                       r"(?:T\(([0-9,]+)\))?", line)
        if ri:
            ng, gs = int(ri.group(1)), int(ri.group(2))
            dims = [int(x) for x in ri.group(3).split(",")]
            arr = np.arange(int(np.prod(dims))).reshape(dims)
            if ri.group(4):
                arr = arr.transpose(
                    [int(x) for x in ri.group(4).split(",")])
            for ids in arr.reshape(ng, gs):
                ids = [int(i) for i in ids]
                groups.append(ids)
                if len({i // group_size for i in ids}) > 1:
                    is_cross = True
        stp = re.search(r"source_target_pairs=\{([0-9,\{\}]*)\}", line)
        if stp:
            for pair in re.findall(r"\{([0-9]+),([0-9]+)\}",
                                   stp.group(1)):
                s, t = int(pair[0]), int(pair[1])
                groups.append([s, t])
                if s // group_size != t // group_size:
                    is_cross = True
        if not groups and devices:
            groups = [list(range(devices))]
            is_cross = devices > group_size
        out.append({"op": op, "bytes": float(nbytes), "cross": is_cross,
                    "groups": groups, "async": is_async})
    # strict parse: any collective mnemonic at op position on a line the
    # main regex did not consume is a counting gap, not a skip
    for sm in _SIGHT.finditer(hlo):
        if sm.group(2) == "-done":
            continue
        bol = hlo.rfind("\n", 0, sm.start()) + 1
        if bol in consumed:
            continue
        eol = hlo.find("\n", sm.start())
        line = hlo[bol:eol if eol != -1 else len(hlo)].strip()
        raise AuditParseError(
            f"unparsed collective line (shape regex missed it): "
            f"{line[:200]!r}")
    return out


def collective_summary(hlo: str, group_size: int,
                       devices: Optional[int] = None) -> List[dict]:
    """JSON-safe :func:`parse_collectives` records (the audit wire form
    priced by ``sim.collectives.priced_collectives``)."""
    return parse_collectives(hlo, group_size, devices)


def collective_bytes(hlo: str, group_size: int) -> Tuple[float, float]:
    """(cross_group_bytes, intra_bytes) over all collectives in optimized
    HLO text; cross = any replica group (brace or iota form) or permute
    pair spanning ICI groups of ``group_size`` consecutive devices."""
    cross = intra = 0.0
    for rec in parse_collectives(hlo, group_size):
        if rec["cross"]:
            cross += rec["bytes"]
        else:
            intra += rec["bytes"]
    return cross, intra


# ---------------------------------------------------------------------------
# model building + lowering (one generic path for every driver family)


def _apply_overrides(cfg, overrides):
    """setattr ``overrides`` onto a model config — lets the verifier and
    tests audit SMALL shapes of the same model family (the driver-default
    transformer is far too heavy for a lint pass)."""
    for k, v in (overrides or {}).items():
        if not hasattr(cfg, k):
            raise SystemExit(
                f"override {k!r} is not a field of {type(cfg).__name__}")
        setattr(cfg, k, v)
    return cfg


def _build_model(model_name: str, machine, batch_size: Optional[int],
                 strategy_path: str, seed: int = 3,
                 dtype: str = "float32", experts: int = 0,
                 overrides: Optional[dict] = None):
    """(model, example_batch) for ``model_name`` with ``strategy_path``
    applied (empty = pure DP) — the same builders the training drivers
    use, so the audited program IS the program a user would run.  A
    strategy carrying an accepted ``__pipeline__`` block builds the SAME
    PipelinedLM the lm driver would run (round 11: accepted pipeline
    blocks get a compiled-HLO audit too)."""
    from flexflow_tpu.strategy import Strategy

    strategies = Strategy.load(strategy_path) if strategy_path else None
    if model_name == "nmt":
        from flexflow_tpu.data import synthetic_token_stream
        from flexflow_tpu.nmt.rnn_model import RnnConfig, RnnModel

        rc = _apply_overrides(RnnConfig(seed=seed, compute_dtype=dtype),
                              overrides)
        if batch_size:
            rc.batch_size = batch_size
        model = RnnModel(rc, machine, strategies)
        gen = synthetic_token_stream(machine, rc.batch_size, rc.seq_length,
                                     rc.vocab_size, seed=5, streams=2)
        return model, tuple(next(gen))
    if model_name in ("transformer", "gpt", "bert"):
        from flexflow_tpu.data import synthetic_token_stream
        from flexflow_tpu.models.transformer import (TransformerConfig,
                                                     TransformerLM)

        tc = _apply_overrides(
            TransformerConfig(seed=seed, compute_dtype=dtype,
                              num_experts=experts), overrides)
        if batch_size:
            tc.batch_size = batch_size
        if model_name == "gpt":
            tc.causal = True
        # explicit None test: a pipeline-only strategy has no per-op
        # entries, so it is len()==0-falsy but must still build the
        # PipelinedLM its block describes
        pp = getattr(strategies, "pipeline", None) \
            if strategies is not None else None
        if pp:
            from flexflow_tpu.parallel.pipeline import PipelinedLM

            model = PipelinedLM(
                machine, pp["stages"], pp["microbatches"],
                num_layers=tc.num_layers, d_model=tc.d_model,
                num_heads=tc.num_heads, d_ff=tc.d_ff,
                vocab_size=tc.vocab_size, seq_length=tc.seq_length,
                batch_size=tc.batch_size, causal=tc.causal,
                compute_dtype=tc.compute_dtype, tp=pp.get("tp", 1) or 1)
        else:
            model = TransformerLM(tc, machine, strategies)
        gen = synthetic_token_stream(machine, tc.batch_size, tc.seq_length,
                                     tc.vocab_size, seed=5, streams=1)
        (toks,) = next(gen)
        return model, (toks, toks)
    from flexflow_tpu.apps.cnn import _builders
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.data import synthetic_batches

    builders = _builders()
    if model_name not in builders:
        raise SystemExit(f"unknown model {model_name!r}")
    size = 299 if model_name.startswith("inception") else 224
    b = batch_size or 16
    cfg = _apply_overrides(
        FFConfig(batch_size=b, input_height=size, input_width=size,
                 num_iterations=1, print_freq=0, seed=seed,
                 compute_dtype=dtype, strategy_file=strategy_path),
        overrides)
    model = builders[model_name](cfg, machine)
    data = synthetic_batches(machine, cfg.batch_size, cfg.input_height,
                             cfg.input_width, mode="ones")
    return model, tuple(next(data))


def _lowered_text(model, batch) -> str:
    if not hasattr(model, "init_opt_state"):
        # PipelinedLM: params-only SGD step (params, tokens, labels)
        params = model.init()
        return model.make_train_step().lower(
            params, *batch).compile().as_text()
    params, state = model.init()
    opt = model.init_opt_state(params)
    step = model.make_train_step()
    return step.lower(params, state, opt, *batch).compile().as_text()


def audit_in_process(model_name: str, devices: int, ici_group: int,
                     strategy_path: str,
                     batch_size: Optional[int] = None,
                     seed: int = 3, dtype: str = "float32",
                     dp_known: Union[Tuple[float, float], dict,
                                     None] = None,
                     experts: int = 0,
                     dcn_calibration: str = "",
                     overrides: Optional[dict] = None) -> dict:
    """Lower ``strategy_path`` AND pure DP on a ``devices``-device machine
    view with ``ici_group``-sized ICI groups; count cross-/intra-tier
    collective bytes AND the structured per-collective records
    (``searched_collectives`` / ``dp_collectives``) plus their predicted
    seconds under the (optionally calibrated) two-tier ring formulas.
    Requires that many live local devices (virtual CPU mesh in
    practice).  ``dp_known`` from an earlier audit of the SAME
    model/shape skips the (expensive, identical) DP lowering — either
    the legacy ``(cross, intra)`` tuple (bytes only, no predicted time)
    or the full audit dict of the earlier run."""
    import jax

    from flexflow_tpu.machine import MachineModel, Topology
    from flexflow_tpu.sim.collectives import priced_collectives

    if len(jax.devices()) < devices:
        raise RuntimeError(
            f"audit needs {devices} devices, process has "
            f"{len(jax.devices())} — use audit_subprocess")
    topo = (Topology.from_calibration(dcn_calibration,
                                      devices_per_ici_group=ici_group)
            if dcn_calibration
            else Topology(devices_per_ici_group=ici_group))
    machine = MachineModel(devices=jax.devices()[:devices], topology=topo)
    out = {"model": model_name, "devices": devices,
           "ici_group": ici_group}
    for key, path in (("searched", strategy_path), ("dp", "")):
        if key == "dp" and isinstance(dp_known, tuple):
            cross, intra = dp_known
            recs = None
        elif key == "dp" and isinstance(dp_known, dict):
            cross = dp_known["dp_cross_bytes"]
            intra = dp_known["dp_intra_bytes"]
            recs = dp_known.get("dp_collectives")
        else:
            model, batch = _build_model(model_name, machine, batch_size,
                                        path, seed, dtype, experts,
                                        overrides)
            recs = parse_collectives(_lowered_text(model, batch),
                                     ici_group, devices)
            cross = sum(r["bytes"] for r in recs if r["cross"])
            intra = sum(r["bytes"] for r in recs if not r["cross"])
        out[f"{key}_cross_bytes"] = cross
        out[f"{key}_intra_bytes"] = intra
        out[f"{key}_collectives"] = recs
        out[f"{key}_pred_s"] = (
            priced_collectives(recs, topo)["seconds"]
            if recs is not None else None)
    out["cross_ratio_dp_over_searched"] = (
        out["dp_cross_bytes"] / max(out["searched_cross_bytes"], 1.0))
    return out


def audit_consistent(audit: dict, simulated_speedup: float) -> bool:
    """Does the compiled program support the simulated two-tier claim?
    A cross-DCN win requires the plan to move STRICTLY fewer cross-tier
    bytes than DP; a claim of more than ~1.2x requires a clear (>=20%)
    byte reduction, not a rounding-level one.  A plan claiming NO win
    (speedup <= 1.05, e.g. the search honestly returned DP) is
    consistent as long as it moves no more than DP."""
    s, d = audit["searched_cross_bytes"], audit["dp_cross_bytes"]
    if simulated_speedup <= 1.05:
        return s <= d
    if d <= 0:
        return s <= 0  # nothing crosses the tier under DP: plan must not
    if s >= d:
        return False
    if simulated_speedup > 1.2 and s > 0.8 * d:
        return False
    return True


def audit_consistent_time(audit: dict, simulated_speedup: float,
                          topo=None,
                          dp_time_s: Optional[float] = None,
                          best_time_s: Optional[float] = None) -> dict:
    """Predicted-seconds upgrade of :func:`audit_consistent` (round 11,
    VERDICT items 3-5/9): price BOTH compiled programs' collectives with
    the calibrated two-tier ring formulas and compare seconds, not bytes.
    This covers the NMT failure mode the byte heuristic could not — a
    plan whose cross bytes look fine but whose total collective volume
    (intra rings included) swamps the claimed win.

    Rules (s/d = searched/dp predicted collective seconds):

    * speedup <= 1.05 (no win claimed): consistent iff s <= 1.05*d —
      honest-DP-like plans may not quietly pay MORE comm than DP;
    * a claimed win requires s <= d (the compiled program must actually
      save communication; d == 0 requires s == 0);
    * speedup > 1.2 with the simulated step times known: the comm saving
      must FUND at least half the claimed win, (d - s) >= 0.5 *
      (dp_time_s - best_time_s); without times, the proportional rule
      s <= 0.8*d applies.

    Falls back to the byte heuristic (mode="bytes") when either side has
    no structured collective records (legacy dp_known tuple) or no
    ``topo`` was given.  Returns {"consistent", "mode",
    "searched_pred_s", "dp_pred_s"}.
    """
    from flexflow_tpu.sim.collectives import priced_collectives

    sc, dc = audit.get("searched_collectives"), audit.get("dp_collectives")
    if sc is None or dc is None or topo is None:
        return {"consistent": audit_consistent(audit, simulated_speedup),
                "mode": "bytes",
                "searched_pred_s": audit.get("searched_pred_s"),
                "dp_pred_s": audit.get("dp_pred_s")}
    s = priced_collectives(sc, topo)["seconds"]
    d = priced_collectives(dc, topo)["seconds"]
    out = {"mode": "time", "searched_pred_s": s, "dp_pred_s": d}
    if simulated_speedup <= 1.05:
        out["consistent"] = s <= 1.05 * d + 1e-12
        return out
    if d <= 0.0:
        out["consistent"] = s <= 0.0
        return out
    if s > d:
        out["consistent"] = False
        return out
    if simulated_speedup > 1.2:
        if dp_time_s is not None and best_time_s is not None \
                and dp_time_s > best_time_s:
            win = dp_time_s - best_time_s
            out["claimed_win_s"] = win
            out["consistent"] = (d - s) >= 0.5 * win
            return out
        out["consistent"] = s <= 0.8 * d
        return out
    out["consistent"] = True
    return out


def audit_subprocess(model_name: str, devices: int, ici_group: int,
                     strategy_path: str,
                     batch_size: Optional[int] = None, seed: int = 3,
                     timeout: float = 900.0,
                     dtype: str = "float32",
                     dp_known: Union[Tuple[float, float], dict,
                                     None] = None,
                     experts: int = 0,
                     dcn_calibration: str = "",
                     overrides: Optional[dict] = None) -> dict:
    """Run :func:`audit_in_process` in a fresh CPU process with
    ``devices`` virtual host devices — callable from any parent (the
    offline search may be running against one real TPU chip, where an
    8-device mesh cannot exist)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "flexflow_tpu.utils.hlo_audit",
           model_name, "--devices", str(devices),
           "--ici-group", str(ici_group), "--seed", str(seed)]
    if strategy_path:
        cmd += ["--strategy", os.path.abspath(strategy_path)]
    if batch_size:
        cmd += ["--batch-size", str(batch_size)]
    if dtype != "float32":
        cmd += ["--dtype", dtype]
    dp_tmp = None
    if isinstance(dp_known, dict):
        # full earlier-audit dict (collectives included): too big for an
        # argv flag — hand it over through a temp file
        import tempfile

        fd, dp_tmp = tempfile.mkstemp(suffix=".json")
        with os.fdopen(fd, "w") as f:
            json.dump({k: dp_known.get(k) for k in
                       ("dp_cross_bytes", "dp_intra_bytes",
                        "dp_collectives")}, f)
        cmd += ["--dp-known-json", dp_tmp]
    elif dp_known is not None:
        cmd += ["--dp-known", f"{dp_known[0]},{dp_known[1]}"]
    if experts:
        cmd += ["--experts", str(experts)]
    if dcn_calibration:
        cmd += ["--dcn-calibration", os.path.abspath(dcn_calibration)]
    if overrides:
        cmd += ["--overrides", json.dumps(overrides)]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout, env=env, cwd=repo)
    finally:
        if dp_tmp:
            os.unlink(dp_tmp)
    if proc.returncode != 0:
        raise RuntimeError(
            f"hlo audit subprocess failed (rc {proc.returncode}):\n"
            f"{proc.stderr[-2000:]}")
    for line in reversed(proc.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(
        f"hlo audit subprocess printed no JSON:\n{proc.stdout[-2000:]}")


def main(argv=None):
    from flexflow_tpu.utils.flags import flag_stream

    args = list(sys.argv[1:] if argv is None else argv)
    opts = {"model": "alexnet", "devices": 8, "ici_group": 4,
            "strategy": "", "batch_size": None, "seed": 3,
            "dtype": "float32", "dp_known": None, "experts": 0,
            "dcn_calibration": "", "overrides": None}
    if args and not args[0].startswith("-"):
        opts["model"] = args.pop(0)
    for a, val in flag_stream(args):
        if a == "--devices":
            opts["devices"] = int(val())
        elif a == "--ici-group":
            opts["ici_group"] = int(val())
        elif a == "--strategy":
            opts["strategy"] = val()
        elif a in ("-b", "--batch-size"):
            opts["batch_size"] = int(val())
        elif a == "--seed":
            opts["seed"] = int(val())
        elif a == "--dtype":
            opts["dtype"] = val()
        elif a == "--dp-known":
            c, i = val().split(",")
            opts["dp_known"] = (float(c), float(i))
        elif a == "--dp-known-json":
            with open(val()) as f:
                opts["dp_known"] = json.load(f)
        elif a == "--experts":
            opts["experts"] = int(val())
        elif a == "--dcn-calibration":
            opts["dcn_calibration"] = val()
        elif a == "--overrides":
            opts["overrides"] = json.loads(val())
    # the audit is defined on the virtual CPU mesh: pin the platform
    # BEFORE any backend init so this process never takes a chip its
    # parent may hold
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={opts['devices']} "
            + os.environ.get("XLA_FLAGS", ""))
    import jax

    jax.config.update("jax_platforms", "cpu")
    out = audit_in_process(opts["model"], opts["devices"],
                           opts["ici_group"], opts["strategy"],
                           opts["batch_size"], opts["seed"],
                           opts["dtype"], opts["dp_known"],
                           opts["experts"], opts["dcn_calibration"],
                           opts["overrides"])
    print(json.dumps(out))


if __name__ == "__main__":
    main()
