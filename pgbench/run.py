"""Run one benchmark cell once and print its result line.

    python pgbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for. The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``; ``checks`` last). Each compared number is also printed beside
its limit as the last lines of standard error. Without a TPU, or with fewer
chips than the cell asks for, the run exits nonzero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from pgbench import harness

    try:
        cell = harness.Cell(args.workload)
        out = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), T_START)
    except harness.BenchError as exc:
        print(f"pgbench: {exc}", file=sys.stderr)
        return 2
    for name, check in out["checks"].items():
        print(f"check {name} = {check['value']!r} (limit {check['limit']!r})",
              file=sys.stderr)
    print(f"correct = {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
