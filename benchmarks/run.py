"""Benchmark harness entry point: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see common.emit) and writes one
machine-diffable ``BENCH_<suite>.json`` per suite to ``--json-dir``: the
suite's schema-consistent records (``{"name", "wall_s", "metrics"}``) plus a
per-stage span breakdown aggregated from the observability tracer (delta
apply, sketch maintenance, cache, flush, kernel execute — see
docs/OBSERVABILITY.md).

``--smoke`` runs the fast subset (kernel micro + engine suites) — the
nightly-CI sanity pass; ``--only NAME`` runs a single suite by name.

Run as a module so relative imports resolve:
  PYTHONPATH=src python -m benchmarks.run [--smoke | --only NAME]

The last line printed is a machine-readable ``bench_run`` JSON summary.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast subset only (nightly CI sanity pass)")
    ap.add_argument("--only", type=str, default=None,
                    help="run a single suite by name")
    ap.add_argument("--json-dir", type=str, default=".",
                    help="directory for the per-suite BENCH_<suite>.json")
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache
    from repro.obs import trace

    use_compile_cache()

    from . import (accuracy_pairs, adaptive_bloom, algo_speedup, common,
                   construction, engine_bench, heuristics, kernels_bench,
                   localcluster, scaling, serving, setexpr_bench,
                   stream_bench, tc_estimators)
    suites = [
        ("kernels", kernels_bench.run),
        ("setexpr", setexpr_bench.run),
        ("engine", engine_bench.run),
        ("stream", stream_bench.run),
        ("localcluster", localcluster.run),
        ("serving", serving.run),
        ("fig3_accuracy", accuracy_pairs.run),
        ("fig4-6_speedup", algo_speedup.run),
        ("table7_tc", tc_estimators.run),
        ("heuristics", heuristics.run),
        ("tableV_construction", construction.run),
        ("fig8_scaling", scaling.run),
        ("adaptive_bloom", adaptive_bloom.run),
    ]
    smoke_suites = {"kernels", "setexpr", "engine", "stream", "localcluster"}
    if args.only is not None:
        suites = [s for s in suites if s[0] == args.only]
        if not suites:
            raise SystemExit(f"unknown suite {args.only!r}")
    elif args.smoke:
        suites = [s for s in suites if s[0] in smoke_suites]

    os.makedirs(args.json_dir, exist_ok=True)
    trace.enable()
    failed = []
    suite_rows = []
    for name, fn in suites:
        print(f"# --- {name}", flush=True)
        common.reset_records()
        trace.clear()
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
        wall = time.perf_counter() - t0
        doc = {"suite": name, "wall_s": round(wall, 3), "ok": name not in failed,
               "records": list(common.RECORDS), "spans": trace.aggregate()}
        path = os.path.join(args.json_dir, f"BENCH_{name}.json")
        with open(path, "w") as fh:
            json.dump(doc, fh, indent=2)
        suite_rows.append({"suite": name, "wall_s": doc["wall_s"],
                           "ok": doc["ok"], "records": len(doc["records"]),
                           "json": path})
    trace.disable()
    if failed:
        print(f"# FAILED suites: {failed}")
    else:
        print("# all benchmark suites completed")
    print(json.dumps({"event": "bench_run", "failed": failed,
                      "suites": suite_rows}))
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
