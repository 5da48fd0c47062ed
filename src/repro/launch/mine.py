"""ProbGraph mining CLI: an engine session, or the edge-sharded TC fold.

Two run modes, both through ``repro.engine``:

  * ``--algos tc,lcc,...``: a multi-query engine session over one shared
    sketch build (:func:`mine_session`).
  * no ``--algos``: the paper's own workload on a device mesh
    (:func:`mine`). The sketch is built once and the TC fold runs with
    ``EnginePlan.shard_edges``: edges are split over every mesh axis and
    every shard runs fixed-size AND+popcount over its slice before a psum.
    Fixed-size sketches mean the shards do identical work: no load
    imbalance, no stragglers from degree skew (paper Fig. 1 panel 5).

``--devices N`` adds ``--xla_force_host_platform_device_count=N`` to
``XLA_FLAGS`` when the module runs as a script, so the sharded fold can run
on N host devices of the CPU backend; it changes nothing on an accelerator.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional

# --devices must take effect before jax initializes its backends
if __name__ == "__main__" and "--devices" in sys.argv:
    _n = sys.argv[sys.argv.index("--devices") + 1]
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS", ""),
        f"--xla_force_host_platform_device_count={_n}"]))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import engine as ENG
from repro.compile_cache import use_compile_cache
from repro.core import graph as G
from repro.core import triangle_count
from repro.distributed import sharding
from repro.obs import metrics, trace


def mine(graph: G.Graph, mesh: Optional[Mesh] = None,
         storage_budget: float = 0.25, num_hashes: int = 2, seed: int = 0):
    """TC estimate with the engine's edge fold sharded over ``mesh``.

    ``mesh`` defaults to every device on one "data" axis. Returns the
    estimate, the sketch build and fold seconds, the Bloom words per
    vertex and the device count.
    """
    if mesh is None:
        mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    t0 = time.perf_counter()
    sess = ENG.session(graph, "bf", storage_budget=storage_budget,
                       num_hashes=num_hashes, seed=seed, shard_edges=True)
    jax.block_until_ready(sess.sketch.data)
    t_build = time.perf_counter() - t0
    t0 = time.perf_counter()
    with sharding.use_rules(mesh):
        tc = float(triangle_count(graph, sess.sketch, plan=sess.plan))
    t_mine = time.perf_counter() - t0
    return {"tc_estimate": tc, "build_s": t_build, "mine_s": t_mine,
            "words": int(sess.sketch.data.shape[1]),
            "devices": int(mesh.devices.size)}


def mine_session(graph: G.Graph, algos: list[str], storage_budget: float = 0.25,
                 num_hashes: int = 2, seed: int = 0, use_kernel: bool = False):
    """Multi-query mining over ONE shared sketch build (engine.session).

    TC, LCC and clustering additionally share a single per-edge cardinality
    pass; 4-clique and local clustering reuse the same sketch. Returns
    {algo: (value, seconds)}.
    """
    t0 = time.perf_counter()
    sess = ENG.session(graph, "bf", storage_budget=storage_budget,
                       num_hashes=num_hashes, seed=seed, use_kernel=use_kernel)
    jax.block_until_ready(sess.sketch.data)
    results = {"build": (sess.stats()["sketch_bytes"], time.perf_counter() - t0)}

    def run_localcluster():
        # deterministic 8-seed batch; report the mean best conductance of
        # the seeds whose sweep found a valid (finite-φ) prefix
        rng = np.random.default_rng(seed + 7)
        seeds = rng.integers(0, graph.n, size=8).astype(np.int32)
        res = sess.local_cluster(seeds, alpha=0.15, eps=1e-4)
        phi = np.asarray(res.best_conductance)
        phi = phi[np.isfinite(phi)]
        return float(phi.mean()) if phi.size else float("nan")

    runners = {
        "tc": lambda: float(sess.triangle_count()),
        "lcc": lambda: float(jnp.mean(sess.local_clustering())),
        "4clique": lambda: float(sess.four_clique_count()),
        "cliques5": lambda: float(sess.five_clique_count()),
        "jp": lambda: int(sess.jarvis_patrick("jaccard", 0.05)[1]),
        "localcluster": run_localcluster,
    }
    for name in algos:
        if name not in runners:
            raise SystemExit(f"unknown algo {name!r}; pick from {sorted(runners)}")
        t0 = time.perf_counter()
        results[name] = (runners[name](), time.perf_counter() - t0)
    return results


def main(argv=None):
    """Run the CLI on ``argv`` (default ``sys.argv[1:]``); returns what it
    printed last as a dict."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--scale", type=int, default=12, help="Kronecker scale")
    ap.add_argument("--edge-factor", type=int, default=16)
    ap.add_argument("--budget", type=float, default=0.25)
    ap.add_argument("--exact", action="store_true", help="also run exact TC")
    ap.add_argument("--algos", type=str, default="",
                    help="comma list (tc,lcc,4clique,cliques5,jp,"
                         "localcluster): run a "
                         "multi-query engine session over one shared sketch "
                         "build")
    ap.add_argument("--use-kernel", action="store_true",
                    help="route BF popcounts through the fused Pallas "
                         "pass (Mosaic on TPU; interpret elsewhere)")
    ap.add_argument("--trace", default=None, metavar="OUT_JSON",
                    help="record spans and write a Chrome-trace/Perfetto "
                         "JSON of the run to this path")
    ap.add_argument("--metrics", action="store_true",
                    help="print a metric-registry snapshot JSON line")
    args = ap.parse_args(argv)

    use_compile_cache()
    if args.trace:
        trace.enable()
        trace.clear()
    g = G.kronecker(args.scale, args.edge_factor, seed=1)
    print(f"graph: n={g.n} m={g.m} d_max={g.d_max}")

    if args.algos:
        res = mine_session(g, args.algos.split(","), storage_budget=args.budget,
                           use_kernel=args.use_kernel)
        sketch_bytes, build_s = res.pop("build")
        print(f"session: sketch={sketch_bytes/1e6:.2f}MB build={build_s:.2f}s")
        for name, (val, secs) in res.items():
            print(f"  {name:8s} = {val:<12.4g} ({secs:.2f}s)")
        # machine-readable twin of the human output (one JSON line)
        out = {
            "event": "mine_session", "n": g.n, "m": g.m, "d_max": g.d_max,
            "budget": args.budget, "use_kernel": args.use_kernel,
            "sketch_bytes": sketch_bytes, "build_s": build_s,
            "algos": {name: {"value": val, "seconds": secs}
                      for name, (val, secs) in res.items()},
        }
        print(json.dumps(out))
        _emit_obs(args)
        return out

    out = mine(g, storage_budget=args.budget)
    print(f"TC_AND={out['tc_estimate']:.0f}  build={out['build_s']:.2f}s "
          f"mine={out['mine_s']:.2f}s devices={out['devices']}")
    if args.exact:
        from repro.core import exact as X
        t0 = time.perf_counter()
        tc = int(X.exact_triangle_count(g))
        print(f"TC_exact={tc} ({time.perf_counter()-t0:.2f}s) "
              f"rel_err={abs(out['tc_estimate']-tc)/max(tc,1):.3f}")
    _emit_obs(args)
    return out


def _emit_obs(args):
    """Shared --trace/--metrics epilogue for both run modes."""
    if args.metrics:
        print(json.dumps({"event": "metrics",
                          "global": metrics.REGISTRY.snapshot()}))
    if args.trace:
        trace.export(args.trace)
        trace.disable()
        print(f"trace -> {args.trace}")


if __name__ == "__main__":
    main()
