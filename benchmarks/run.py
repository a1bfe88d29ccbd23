"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

``setup_s`` runs from the next statement, before anything else is
imported, to the instant the measured window opens.  The last line of
standard output is the contract's JSON object; every other line (the
set-up phases among them) comes before it.  ``--cpu-rehearsal`` is for
debugging at tiny sizes on the CPU: it is an argument, never a default,
and it cannot end in ``"correct": true`` or exit code 0.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--root", default=ROOT,
                    help="directory holding BENCHMARK.json (tests point "
                         "this at a copy with files added)")
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmarks import harness

    return harness.run(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
