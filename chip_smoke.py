"""Smoke test of ProbGraph on a TPU: the mining and serving paths at full size.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py              # one chip: kernels, mining, serving
    python chip_smoke.py --chips 4    # four chips: the edge-sharded fold only

The graph is the Graph500 Kronecker graph at scale 16, edge factor 16,
seed 1 (n = 65,536, m = 910,200, d_max = 9,729): the largest one whose
padded adjacency (2.4 GiB) one 16 GB chip holds with room for the stream's
headroom copy and the gathers' temporaries. Every phase runs in this one
process, through the entry points a user calls:

  kernels  Bloom sketches at budgets 0.25, 4 and 16 (W = 8, 116 and 462
           words): the compiled k-way AND (k = 2, 3, 4) in gather form and
           the 2-way AND in dense (sweep-cut) form. Pallas popcounts must
           equal the jnp lowering and a numpy popcount bit for bit, the
           compiled HLO must hold the Mosaic kernel (``tpu_custom_call``)
           and the compiled expression must not run in interpret mode.
  mining   ``repro.launch.mine`` sessions of tc,lcc,jp,localcluster at
           budget 4, once on the jnp path and once on the kernel path. The
           two runs' popcounts must be identical and their results equal;
           the kernel run must lower to Mosaic. TC's relative error against
           ``core/exact.py`` is printed at scale 14, not gated.
  serving  ``repro.launch.stream`` replays 4 delta batches with the CLI's
           query mix and ``--verify``; every batch must match a from-scratch
           static session, and at least one delta must take the donated
           device update.

``--chips 4`` runs only the multi-chip path: TC and LCC with
``EnginePlan.shard_edges`` on a 4-device mesh, compared with the same fold
on one device; the edge array must be split over the 4 devices and the
compiled program must hold the all-reduce.

The last line printed is ``{"ok": true, "device": {...}}`` with the device
as JAX reports it; it is printed only when every phase passed on a TPU.
Without a TPU, or when any phase fails, the script exits nonzero and prints
no such line. Seconds printed along the way are set-up evidence, not
benchmark metrics.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SCALE = 16            # Graph500 scale (n = 2**SCALE)
EDGE_FACTOR = 16      # Graph500 edge factor
GRAPH_SEED = 1        # the seed launch/mine.py generates its graph with
KERNEL_BUDGETS = (0.25, 4.0, 16.0)
MINE_BUDGET = 4.0
KERNEL_TUPLES = 1 << 16
DENSE_ROWS = 8 * 512  # a sweep batch: 8 seeds x sweep_cap 512
STREAM_BATCHES = 4
EXACT_SCALE = 14      # exact TC reference (see report_tc_error)
EXACT_EDGE_CHUNK = 8192
_T0 = time.perf_counter()


class SmokeFailure(RuntimeError):
    """A check of the smoke test did not hold."""


def require(cond: bool, what: str) -> None:
    """Fail the smoke test unless ``cond`` holds."""
    if not cond:
        raise SmokeFailure(what)


def log(phase: str, msg: str) -> None:
    """One progress line on standard output, stamped with the seconds
    since start."""
    print(f"[{time.perf_counter() - _T0:7.1f}s {phase}] {msg}", flush=True)


def np_popcount(words: np.ndarray) -> np.ndarray:
    """Popcount over the trailing uint32 word axis, in numpy."""
    return np.unpackbits(np.ascontiguousarray(words).view(np.uint8),
                         axis=-1).sum(axis=-1).astype(np.int32)


def tpu_device(chips: int):
    """The first TPU device, or exit nonzero when JAX finds none."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})")
    if len(jax.devices()) < chips:
        sys.exit(f"chip_smoke: needs {chips} chips, JAX found "
                 f"{len(jax.devices())}")
    return dev


def compile_mosaic(fn, what: str, *args):
    """Compile ``fn`` for ``args``; fail unless the program holds a Mosaic
    kernel (``tpu_custom_call``). Returns the compiled callable."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    require("tpu_custom_call" in compiled.as_text(),
            f"{what}: no tpu_custom_call in the compiled HLO")
    return compiled


def check_kernels(g, budgets=KERNEL_BUDGETS, n_tuples=KERNEL_TUPLES,
                  dense_rows=DENSE_ROWS) -> None:
    """Pallas == jnp == numpy popcounts for the compiled k-way ANDs.

    The sketches are built on the host (``build_bloom_np``): the device
    build is the mining phase's, and three more device builds at three
    widths would add minutes of compilation to the smoke.
    """
    import jax.numpy as jnp

    from repro.core import sketches as SK
    from repro.engine import setexpr

    rng = np.random.default_rng(0)
    edges = np.asarray(g.edges)
    pick = rng.choice(edges.shape[0], n_tuples,
                      replace=edges.shape[0] < n_tuples)
    tuples = np.concatenate(
        [edges[pick], rng.integers(0, g.n, size=(n_tuples, 2))],
        axis=1).astype(np.int32)
    for budget in budgets:
        host = SK.build_bloom_np(
            g, SK.bloom_words_for_budget(g.n, g.m, budget), num_hashes=2)
        sk = SK.SketchSet(data=jnp.asarray(host), kind="bf", num_hashes=2,
                          k=0, seed=0, n=g.n)
        log("kernels", f"budget={budget} W={host.shape[1]} sketch built")
        for k in (2, 3, 4):
            expr = setexpr.and_all(*setexpr.rows(k))
            kern = setexpr.compile_expr(expr, use_kernel=True)
            ref = setexpr.compile_expr(expr, use_kernel=False)
            require(kern.interpret is False,
                    f"W={host.shape[1]} k={k}: kernel runs in interpret mode")
            tk = jnp.asarray(tuples[:, :k])
            t0 = time.perf_counter()
            got = np.asarray(compile_mosaic(
                kern.ones, f"W={host.shape[1]} k={k}", sk.data, tk)(
                    sk.data, tk))
            secs = time.perf_counter() - t0
            rows = host[tuples[:, 0]]
            for i in range(1, k):
                rows = rows & host[tuples[:, i]]
            want = np_popcount(rows)
            require(np.array_equal(got, want),
                    f"W={host.shape[1]} k={k}: Pallas != numpy popcounts")
            require(np.array_equal(np.asarray(ref.ones(sk.data, tk)), want),
                    f"W={host.shape[1]} k={k}: jnp != numpy popcounts")
            log("kernels", f"W={host.shape[1]} k={k} T={n_tuples}: "
                           f"pallas == jnp == numpy (compile+run {secs:.2f}s)")
        a = host[tuples[:dense_rows, 0]]
        b = host[tuples[:dense_rows, 1]]
        u, v = setexpr.rows(2)
        dense = setexpr.compile_expr(u & v, use_kernel=True)
        ja, jb = jnp.asarray(a), jnp.asarray(b)
        got = compile_mosaic(dense.ones_rows, f"W={host.shape[1]} dense",
                             ja, jb)(ja, jb)
        require(np.array_equal(np.asarray(got), np_popcount(a & b)),
                f"W={host.shape[1]} dense: Pallas != numpy popcounts")
        log("kernels", f"W={host.shape[1]} dense k=2 E={dense_rows}: "
                       f"pallas == numpy")


def check_mining(scale=SCALE, budget=MINE_BUDGET) -> None:
    """Two ``launch/mine.py`` session runs (jnp, kernel) that must agree."""
    from repro import engine as ENG
    from repro.core import graph as G, sketches as SK
    from repro.engine import setexpr
    from repro.launch import mine as mine_cli

    argv = ["--scale", str(scale), "--edge-factor", str(EDGE_FACTOR),
            "--budget", str(budget), "--algos", "tc,lcc,jp,localcluster"]
    runs = {}
    for use_kernel in (False, True):
        log("mining", f"launch.mine {' '.join(argv)}"
                      f"{' --use-kernel' if use_kernel else ''}")
        runs[use_kernel] = mine_cli.main(
            argv + (["--use-kernel"] if use_kernel else []))["algos"]
    for name in ("tc", "lcc", "jp", "localcluster"):
        a, b = runs[False][name]["value"], runs[True][name]["value"]
        rel = abs(a - b) / max(abs(a), 1e-30)
        log("mining", f"{name}: jnp={a!r} kernel={b!r} rel_diff={rel:.3g}")
        require(rel <= 1e-6, f"mining {name}: kernel and jnp runs differ")

    # the two runs' popcounts, from the plans their sessions resolved
    g = G.kronecker(scale, EDGE_FACTOR, seed=GRAPH_SEED)
    sk = SK.build(g, "bf", budget, num_hashes=2, seed=0)
    plans = {uk: ENG.plan_for(g, sk, use_kernel=uk) for uk in (False, True)}
    ones = {uk: np.asarray(ENG.tuple_cardinality_ones(sk, g.edges, plan))
            for uk, plan in plans.items()}
    require(np.array_equal(ones[False], ones[True]),
            "mining: kernel and jnp popcounts differ")
    log("mining", f"popcounts over all {g.m} edges identical "
                  f"(sum {int(ones[True].sum())})")
    kp = plans[True]
    u, v = setexpr.rows(2)
    ce = setexpr.compile_expr(u & v, block_e=kp.block_e, block_w=kp.block_w,
                              use_kernel=True)
    require(ce.interpret is False, "mining: kernel path runs in interpret mode")
    compile_mosaic(lambda gr, s: ENG.edge_cardinalities(gr, s, kp),
                   "mining: the kernel run's edge pass", g, sk)
    log("mining", "kernel run lowers to Mosaic (tpu_custom_call, "
                  "interpret=False)")


def report_tc_error(scale=EXACT_SCALE, budget=MINE_BUDGET) -> None:
    """Print a kernel-path session's TC estimate against ``core/exact.py``
    at ``scale``; the error is reported, not gated.

    The scale is 14, not 16: the exact count gallops over every padded
    adjacency row of every edge (m * d_max = 8.9e9 searches at scale 16,
    39x scale 13's), which the smoke's time limit cannot take on top of
    its compilations.
    """
    from repro.core import exact as X
    from repro.core import graph as G
    from repro.launch import mine as mine_cli

    g = G.kronecker(scale, EDGE_FACTOR, seed=GRAPH_SEED)
    tc = mine_cli.mine_session(g, ["tc"], storage_budget=budget,
                               use_kernel=True)["tc"][0]
    t0 = time.perf_counter()
    exact = int(X.exact_triangle_count(g, edge_chunk=EXACT_EDGE_CHUNK))
    log("mining", f"scale {scale}: exact TC = {exact} "
                  f"({time.perf_counter() - t0:.2f}s), budget-{budget} "
                  f"kernel-path estimate {tc!r}, "
                  f"rel_err={abs(tc - exact) / max(exact, 1):.4f}")


def check_serving(scale=SCALE, batches=STREAM_BATCHES) -> None:
    """``launch/stream.py --verify``: exact answers, donated updates."""
    from repro.launch import stream as stream_cli

    argv = ["--scale", str(scale), "--edge-factor", str(EDGE_FACTOR),
            "--batches", str(batches), "--verify"]
    log("serving", f"launch.stream {' '.join(argv)}")
    summary = stream_cli.main(argv)
    require(summary["verify_all_exact"] is True,
            "serving: verify_all_exact is not true")
    donated = summary["stream"]["traffic"]["donated_updates"]
    log("serving", f"verify_all_exact=true; deltas donated: {donated} of "
                   f"{summary['batches']}")
    require(donated >= 1, "serving: no delta took the donated update")


def check_sharded(chips: int, scale=SCALE, budget=MINE_BUDGET) -> None:
    """TC and LCC with ``shard_edges`` on a ``chips``-device mesh == the
    same fold on one device."""
    import dataclasses

    import jax
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro import engine as ENG
    from repro.core import graph as G, sketches as SK
    from repro.core import triangle_count
    from repro.core.algorithms.tc import local_clustering_coefficient
    from repro.distributed import sharding

    g = G.kronecker(scale, EDGE_FACTOR, seed=GRAPH_SEED)
    sk = SK.build(g, "bf", budget, num_hashes=2, seed=0)
    plan = ENG.plan_for(g, sk)
    tc1 = float(triangle_count(g, sk, plan=plan))
    lcc1 = np.asarray(local_clustering_coefficient(g, sk, plan=plan))
    log("sharded", f"one device: tc={tc1!r} mean lcc={lcc1.mean()!r}")

    # the edge list placed over the mesh as a multi-chip deployment holds
    # it; Auto axes, so the jitted program is partitioned by the compiler
    mesh = jax.make_mesh((chips,), ("data",), axis_types=(AxisType.Auto,))
    require(g.m % chips == 0, f"m={g.m} does not split over {chips} chips")
    edges = jax.device_put(g.edges, NamedSharding(mesh, P("data", None)))
    shards = {s.device: s.data.shape for s in edges.addressable_shards}
    require(len(shards) == chips and all(
        shape == (g.m // chips, 2) for shape in shards.values()),
        f"edge array is not split over {chips} devices: {shards}")
    log("sharded", f"edge array split over {len(shards)} devices, "
                   f"{g.m // chips} edges each")
    gs = dataclasses.replace(g, edges=edges)
    plan_s = plan.with_(shard_edges=True)

    def tc_lcc(gr, s):
        """The sharded TC fold and the sharded per-edge LCC pass."""
        return (triangle_count(gr, s, plan=plan_s),
                local_clustering_coefficient(gr, s, plan=plan_s))

    with sharding.use_rules(mesh):
        compiled = jax.jit(tc_lcc).lower(gs, sk).compile()
        require("all-reduce" in compiled.as_text(),
                "sharded: no all-reduce in the compiled program")
        tc4, lcc4 = compiled(gs, sk)
    tc4, lcc4 = float(tc4), np.asarray(lcc4)
    log("sharded", f"{chips} devices: tc={tc4!r} mean lcc={lcc4.mean()!r}")
    require(abs(tc4 - tc1) <= 1e-5 * abs(tc1),
            f"sharded TC {tc4!r} != one-device TC {tc1!r}")
    require(np.allclose(lcc4, lcc1, rtol=1e-5, atol=1e-7),
            "sharded LCC != one-device LCC")
    log("sharded", f"tc rel_diff={abs(tc4 - tc1) / abs(tc1):.3g}, "
                   f"lcc max_abs_diff={np.max(np.abs(lcc4 - lcc1)):.3g}")


def main(argv=None) -> None:
    """Run the phases for ``--chips``; print the device line last."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the edge-sharded fold on 4 chips")
    args = ap.parse_args(argv)

    dev = tpu_device(args.chips)
    import jax

    from repro.compile_cache import use_compile_cache
    from repro.core import graph as G

    log("setup", f"device {dev.device_kind} x{len(jax.devices())}, "
                 f"jax {jax.__version__}, compile cache {use_compile_cache()}")
    t_all = time.perf_counter()
    if args.chips == 4:
        check_sharded(args.chips)
    else:
        t0 = time.perf_counter()
        g = G.kronecker(SCALE, EDGE_FACTOR, seed=GRAPH_SEED)
        log("setup", f"graph500 scale {SCALE}: n={g.n} m={g.m} "
                     f"d_max={g.d_max} ({time.perf_counter() - t0:.2f}s)")
        check_kernels(g)
        del g
        check_mining()
        report_tc_error()
        check_serving()
    log("done", f"all phases passed in {time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
