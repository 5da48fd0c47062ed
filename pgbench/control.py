"""Readings for the output check's limits: the program over many seeds, and
the control, which has to come out not correct.

    python pgbench/control.py --workload <cell> --seeds 1,2,3 [--control-seeds 4,5,6] [--seconds 0]

One process, on the chip, at the cell's own size. For each program seed it
runs the cell as the benchmark does (``--seconds`` of window, 0 for one
step) and prints the compared numbers; for each control seed it prints the
numbers of the control: the plain reference put in the program's place
with its estimates computed in bfloat16, the precision below the float32
the configuration states.

Each reading is one JSON line on standard output. The benchmark's own runs
never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def mine_control_outputs(config: dict, traffic: dict, seed: int) -> dict:
    """The reference in the program's place, estimates in bfloat16."""
    import ml_dtypes

    from pgbench.checks import mine as CM
    from pgbench.reference import mining as RM

    ref = CM.mine_reference(config, traffic, seed,
                                 dtype=ml_dtypes.bfloat16)
    n = ref["lcc"].shape[0]
    sure = ref["jp_sure"]
    job = {"sketch": ref["sketch"], "cards": ref["cards"].astype(np.float32),
           "tc": float(ref["cards"].astype(np.float64).sum() / 3.0),
           "lcc": ref["lcc"].astype(ml_dtypes.bfloat16).astype(np.float32),
           "jp_labels": RM.component_min_labels(n, ref["edges"][sure])}
    return {"edges": ref["edges"], "jobs": {"control": job}}


def control_numbers(cell, seed: int) -> dict:
    """The control's compared numbers for one seed."""
    from pgbench.checks import mine as CM

    outputs = mine_control_outputs(cell.config, cell.traffic, seed)
    return CM.numbers(cell.traffic, cell.config, outputs, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)

    from pgbench import harness

    cell = harness.Cell(args.workload)
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        t0 = time.perf_counter()
        out = harness.run_cell(cell, seed, args.seconds, False, t0)
        print(json.dumps({"side": "program", "seed": seed,
                          "correct": out["correct"],
                          "numbers": {k: v["value"]
                                      for k, v in out["checks"].items()},
                          "metrics": out["metrics"],
                          "seconds": time.perf_counter() - t0}), flush=True)
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        t0 = time.perf_counter()
        nums = control_numbers(cell, seed)
        print(json.dumps({"side": "control", "seed": seed, "numbers": nums,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
