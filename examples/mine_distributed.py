"""Distributed ProbGraph mining demo (the paper's workload on a device mesh).

Spawns 8 host devices, builds Bloom sketches once, runs the engine's
edge-sharded triangle count (``EnginePlan.shard_edges``: a shard_map over
every mesh axis with a psum), and compares against the exact count. The
same code path runs on a multi-chip TPU mesh (launch/mine.py).

Run:  PYTHONPATH=src python examples/mine_distributed.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time  # noqa: E402

import jax  # noqa: E402

from repro.core import graph as G  # noqa: E402
from repro.core import exact as X  # noqa: E402
from repro.launch.mine import mine  # noqa: E402


def main():
    g = G.kronecker(12, 16, seed=1)
    print(f"graph: n={g.n} m={g.m} d_max={g.d_max}")
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    out = mine(g, mesh, storage_budget=0.25, num_hashes=1)
    print(f"devices={out['devices']} words/vertex={out['words']}")
    print(f"sketch build: {out['build_s']:.2f}s   mining: {out['mine_s']:.2f}s")
    t0 = time.time()
    tc = int(X.exact_triangle_count(g))
    t_exact = time.time() - t0
    rel = abs(out["tc_estimate"] - tc) / max(tc, 1)
    print(f"TC: estimate={out['tc_estimate']:.0f} exact={tc} "
          f"rel_err={rel:.3f} (exact took {t_exact:.2f}s)")


if __name__ == "__main__":
    main()
