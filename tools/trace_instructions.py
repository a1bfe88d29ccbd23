"""Device self time of EVERY instruction in a cell's newest trace (the
benchmark's ``operators`` line prints the top fifteen), to
``chiprun_out/instructions.<cell>.json``: ``{instruction: seconds inside
the traced window, mean of the devices}``.  Run it after a ``--trace 1``
run of the cell, in the same checkout:

    python3 benchmarks/run.py --workload <cell> --seed 1 --trace 1
    python3 tools/trace_instructions.py <cell>

With the compiled step's text (``FFModel.compile_train_step(..)
.as_text()``, made on the chip or for a described v5e) and the number of
traced steps (``traced_steps`` of the run's ``operators`` line) it joins
that file, wherever it was brought, to the operators and prints ms a step
by operator: forward, the forward a recomputed block runs once more
(instructions under ``rematted_computation/``, which the operator table
charges to ``backward`` together with the backward proper) and backward:

    python3 tools/trace_instructions.py <cell> <step.hlo.txt> <steps>

No JAX device is touched.
"""

import collections
import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(cell: str) -> int:
    from benchmarks import program_trace, trace_reduce as tr

    files = glob.glob(os.path.join(ROOT, ".bench_cache", "scratch", cell,
                                   "trace", "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise SystemExit(f"no trace of {cell} under .bench_cache/scratch")
    events = program_trace.load_xplane(max(files, key=os.path.getmtime))
    window = [e for e in events if e["name"] == tr.WINDOW_SPAN]
    w0 = window[0]["start_ns"] if window else float("-inf")
    w1 = w0 + window[0]["dur_ns"] if window else float("inf")
    by_device = collections.defaultdict(list)
    for e in events:
        if e["plane"].startswith("/device:") and e["line"] == tr.OPS_LINE \
                and e["start_ns"] < w1 and e["start_ns"] + e["dur_ns"] > w0:
            by_device[e["plane"]].append(e)
    seconds = collections.defaultdict(float)
    for evs in by_device.values():
        for e, ns in tr.self_times(evs):
            seconds[tr.split_name(e["name"])[0]] += ns * 1e-9 / len(by_device)
    out = os.path.join(ROOT, "chiprun_out", f"instructions.{cell}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(dict(sorted(seconds.items(), key=lambda kv: -kv[1])), f)
    print(f"{len(seconds)} instructions, {sum(seconds.values()):.4f} s -> "
          f"{out}")
    return 0


RECOMPUTED = "recomputed"
# a scope of its own for the second forward, so that the table's rules
# for fusions and rewritten custom calls hold for it as for any operator
_REMATTED = re.compile(r"rematted_computation/(\w+)")


def by_operator(cell: str, hlo_file: str, steps: int) -> int:
    """Print ``{operator: {forward, recomputed, backward}}`` in ms a
    step, largest first, and the three sums.  Refuses a text that lacks
    an instruction of the trace: it is of another program."""
    from flexflow_tpu.obs import optrace

    with open(os.path.join(ROOT, "chiprun_out",
                           f"instructions.{cell}.json")) as f:
        seconds = json.load(f)
    with open(hlo_file) as f:
        table = optrace.operator_table(
            _REMATTED.sub(RECOMPUTED + r".\1", f.read()))
    missing = sorted(set(seconds) - set(table))
    if missing:
        raise SystemExit(f"{hlo_file} has no {missing[:5]} ({len(missing)} "
                         f"of {len(seconds)}): not the traced program")
    ms = collections.defaultdict(lambda: collections.defaultdict(float))
    for name, s in seconds.items():
        operator, part = table[name]
        if operator.startswith(RECOMPUTED + "."):
            operator, part = operator[len(RECOMPUTED) + 1:], RECOMPUTED
        ms[operator or "(none)"][part] += 1e3 * s / steps
    parts = (optrace.FORWARD, RECOMPUTED, optrace.BACKWARD)
    rows = {op: {p: round(v, 3) for p, v in d.items()}
            for op, d in sorted(ms.items(),
                                key=lambda kv: -sum(kv[1].values()))}
    print(json.dumps({"ms_per_step": rows, "sum": {
        p: round(sum(d.get(p, 0.0) for d in ms.values()), 3)
        for p in parts}}))
    return 0


if __name__ == "__main__":
    if len(sys.argv) > 2:
        sys.exit(by_operator(sys.argv[1], sys.argv[2], int(sys.argv[3])))
    sys.exit(main(sys.argv[1]))
