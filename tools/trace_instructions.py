"""Device self time of EVERY instruction in a cell's newest trace (the
benchmark's ``operators`` line prints the top fifteen), to
``chiprun_out/instructions.<cell>.json``: ``{instruction: seconds inside
the traced window, mean of the devices}``.  Run it after a ``--trace 1``
run of the cell, in the same checkout:

    python3 benchmarks/run.py --workload <cell> --seed 1 --trace 1
    python3 tools/trace_instructions.py <cell>

Join it with ``FFModel.operator_table()`` (or ``obs/optrace.py`` on the
step's compiled text) for the operator and pass of each.  No JAX device
is touched.
"""

import collections
import glob
import json
import os
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


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
