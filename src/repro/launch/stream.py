"""Streaming replay driver: timestamped edge deltas + interleaved queries.

Generates a Kronecker power-law graph, withholds a fraction of its edges as
a timestamped arrival stream, and replays them in delta batches against a
:class:`repro.stream.StreamSession` — interleaving each delta with a batched
query flush (similarity / membership / link prediction / triangle count /
local clustering)
through :class:`repro.stream.BatchedQueryServer`. Per batch it reports what
incremental maintenance saved (rows updated in place vs selectively rebuilt
vs the full-rebuild alternative), the host → device bytes the delta uploaded
(the device-resident path's contract: proportional to the delta, never a
full-graph snapshot) and the servers' latency/staleness stats;
``--verify`` additionally checks every answer against a from-scratch
``engine.session`` on the equivalent static graph (exact match under the
default strict policy).

  PYTHONPATH=src python -m repro.launch.stream --scale 10 --batches 12 --verify
  PYTHONPATH=src python -m repro.launch.stream --checkpoint-dir /tmp/ck --restore

The last line printed is a machine-readable JSON summary. With
``--verify`` the run exits nonzero unless every batch matched exactly.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro import engine as ENG
from repro.compile_cache import use_compile_cache
from repro.core import graph as G
from repro.core import sketches as SK
from repro.obs import metrics, trace
from repro.stream import (BatchedQueryServer, DynamicGraph, ErrorBudgetPolicy,
                          StreamSession)


def build_stream(scale: int, edge_factor: int, stream_frac: float, seed: int):
    """Kronecker edges split into (initial graph, timestamped arrivals)."""
    g = G.kronecker(scale, edge_factor, seed=seed)
    rng = np.random.default_rng(seed + 1)
    edges = np.asarray(g.edges)
    order = rng.permutation(edges.shape[0])  # arrival order == timestamp
    split = int((1.0 - stream_frac) * edges.shape[0])
    return g.n, edges[order[:split]], edges[order[split:]]


def verify_against_static(st: StreamSession, pairs: np.ndarray,
                          lc_seed: int | None = None) -> dict:
    """From-scratch engine.session on the equivalent static graph.

    The static adjacency is padded to the stream's row capacity: padding
    changes no answer, and every batch's static build then reuses the
    programs compiled for the stream's own adjacency shape.
    """
    gs = G.from_edge_array(st.dyn.n, st.dyn.edge_array(),
                           pad_to_max_degree=st.dyn.capacity)
    mt = st.maintainer
    sk = None
    if mt is not None:
        sk = SK.build(gs, mt.kind, words=mt.words or None, k=mt.k or None,
                      num_hashes=mt.num_hashes, seed=mt.seed)
    sess = ENG.session(gs, sk, plan=st.session.plan)
    tc_static = float(sess.triangle_count())
    tc_stream = float(st.triangle_count())
    sim_static = np.asarray(sess.similarity(pairs, "jaccard"))
    sim_stream = np.asarray(st.similarity(pairs, "jaccard"))
    exact = (tc_stream == tc_static
             and np.array_equal(sim_stream, sim_static))
    out = {
        "tc_abs_err": abs(tc_stream - tc_static),
        "sim_max_err": float(np.max(np.abs(sim_stream - sim_static)))
        if pairs.size else 0.0,
    }
    if lc_seed is not None:
        lc_static = sess.local_cluster(np.array([lc_seed], np.int32),
                                       alpha=0.15, eps=1e-3)
        lc_stream = st.local_cluster(np.array([lc_seed], np.int32),
                                     alpha=0.15, eps=1e-3)
        out["lc_phi_abs_err"] = abs(
            float(lc_static.best_conductance[0])
            - float(lc_stream.best_conductance[0]))
        exact = exact and np.array_equal(
            np.asarray(lc_static.conductance), np.asarray(lc_stream.conductance))
    out["exact_match"] = exact
    return out


def main(argv=None):
    """Run the replay on ``argv`` (default ``sys.argv[1:]``); returns the
    JSON summary it printed last."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=10, help="Kronecker scale")
    ap.add_argument("--edge-factor", type=int, default=8)
    ap.add_argument("--kind", default="bf",
                    choices=["bf", "kh", "1h", "kmv", "exact"])
    ap.add_argument("--budget", type=float, default=0.25)
    ap.add_argument("--batches", type=int, default=12)
    ap.add_argument("--stream-frac", type=float, default=0.3,
                    help="fraction of edges withheld as the arrival stream")
    ap.add_argument("--delete-frac", type=float, default=0.1,
                    help="deletions per batch as a fraction of its inserts")
    ap.add_argument("--queries", type=int, default=64,
                    help="similarity pairs per interleaved query batch")
    ap.add_argument("--tolerance", type=float, default=0.0,
                    help="error-budget rel_tolerance (0 = strict/bit-exact)")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--no-cache", action="store_true",
                    help="disable the serving-tier result cache")
    ap.add_argument("--async-serving", action="store_true",
                    help="run the server's background flush worker: deltas "
                         "overlap query service on snapshot-isolated views")
    ap.add_argument("--verify", action="store_true",
                    help="check answers against a from-scratch static session")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=5)
    ap.add_argument("--restore", action="store_true",
                    help="resume from the latest checkpoint in --checkpoint-dir")
    ap.add_argument("--trace", default=None, metavar="OUT_JSON",
                    help="record spans and write a Chrome-trace/Perfetto "
                         "JSON of the replay to this path")
    ap.add_argument("--metrics", action="store_true",
                    help="embed metric-registry snapshots in the summary")
    args = ap.parse_args(argv)

    use_compile_cache()
    if args.trace:
        trace.enable()
        trace.clear()
    n, initial, arrivals = build_stream(args.scale, args.edge_factor,
                                        args.stream_frac, args.seed)
    kind = None if args.kind == "exact" else args.kind
    # the stream is regenerated from these parameters on restore — any drift
    # would silently replay wrong/duplicate arrival chunks, so they are
    # stored with every checkpoint and validated here
    stream_cfg = {"scale": args.scale, "edge_factor": args.edge_factor,
                  "stream_frac": args.stream_frac, "batches": args.batches,
                  "seed": args.seed, "kind": args.kind}

    if args.restore:
        if not args.checkpoint_dir:
            raise SystemExit("--restore requires --checkpoint-dir")
        st = StreamSession.restore(args.checkpoint_dir)
        if st.extra and st.extra != stream_cfg:
            raise SystemExit(
                f"checkpoint stream config {st.extra} does not match the "
                f"requested flags {stream_cfg}; rerun with matching flags")
        print(f"restored: version={st.version} n={st.dyn.n} m={st.dyn.m}")
    else:
        st = StreamSession(
            DynamicGraph.from_edges(n, initial), kind=kind,
            storage_budget=args.budget,
            policy=ErrorBudgetPolicy(rel_tolerance=args.tolerance))
    # admission policy: the five per-batch queries below auto-flush on the
    # fifth submit (max_batch) — no hand-rolled flush loop; max_wait_s keeps
    # a straggler batch from waiting forever under other traffic shapes
    server = BatchedQueryServer(st, max_batch=5, max_wait_s=0.25,
                                cache=not args.no_cache,
                                async_flush=args.async_serving)
    chunks = np.array_split(arrivals, args.batches)
    print(f"stream: n={n} initial_m={st.dyn.m} arrivals={arrivals.shape[0]} "
          f"batches={args.batches} kind={args.kind}")

    _ = st.session.edge_cardinalities()  # warm the shared pass
    batch_rows = []
    for b in range(st.version, args.batches):
        # per-batch rng keyed on (seed, b): a restored run draws the same
        # deletions/queries the uninterrupted run would have at this batch
        rng = np.random.default_rng([args.seed + 2, b])
        ins = chunks[b]
        cur = st.dyn.edge_array()
        n_del = min(int(args.delete_frac * max(len(ins), 1)), cur.shape[0])
        dels = cur[rng.choice(cur.shape[0], size=n_del, replace=False)] \
            if n_del else None
        t0 = time.perf_counter()
        info = st.apply_delta(ins, dels)
        dt_delta = time.perf_counter() - t0

        qpairs = rng.integers(0, n, size=(args.queries, 2)).astype(np.int32)
        t0 = time.perf_counter()
        server.submit_similarity(qpairs, "jaccard")
        server.submit_membership(int(rng.integers(0, n)),
                                 rng.integers(0, n, size=16))
        server.submit_link_prediction(int(rng.integers(0, n)), top_k=4)
        lc_seed = int(rng.integers(0, n))
        lc_rid = server.submit_local_cluster(lc_seed, alpha=0.15, eps=1e-3)
        tc_rid = server.submit_triangle_count()  # 5th submit -> auto-flush
        answers = server.flush()                 # already answered; drains
        dt_query = time.perf_counter() - t0

        lc = answers[lc_rid].value
        row = {"batch": b, "m": st.dyn.m, "delta_s": round(dt_delta, 4),
               "query_s": round(dt_query, 4),
               "tc": answers[tc_rid].value,
               "localcluster": {"size": lc["size"],
                                "conductance": lc["conductance"]},
               **info}
        if args.verify:
            row["verify"] = verify_against_static(st, qpairs, lc_seed)
        batch_rows.append(row)
        print(f"[{b:03d}] m={row['m']} +{info['inserted']} -{info['deleted']} "
              f"tc={row['tc']:.1f} recomputed={info['cards_recomputed']}"
              f"/carried={info['cards_carried']} "
              f"rebuilt={info['rows_rebuilt_now']} "
              f"lc(|C|={lc['size']},phi={lc['conductance']:.3f}) "
              f"upload={info['bytes_uploaded'] / 1024:.1f}KiB "
              f"delta={dt_delta*1e3:.1f}ms query={dt_query*1e3:.1f}ms"
              + (f" exact={row['verify']['exact_match']}" if args.verify
                 else ""))
        if args.checkpoint_dir and (b + 1) % args.checkpoint_every == 0:
            path = st.save(args.checkpoint_dir, extra=stream_cfg)
            print(f"      checkpoint -> {path}")

    server_stats = server.stats()   # before close(), which drops the cache
    server.close()                  # flush-then-detach
    summary = {"event": "stream_replay", "n": n, "final_m": st.dyn.m,
               "batches": len(batch_rows), "stream": st.stats(),
               "server": server_stats,
               # null (not a vacuous true) when no batch was verified
               "verify_all_exact": all(r["verify"]["exact_match"]
                                       for r in batch_rows)
               if args.verify and batch_rows else None}
    if args.metrics:
        summary["metrics"] = {"global": metrics.REGISTRY.snapshot(),
                              "stream": st.metrics.snapshot(),
                              "server": server.metrics.snapshot()}
    if args.trace:
        trace.export(args.trace)
        trace.disable()
        summary["trace"] = args.trace
    print(json.dumps(summary))
    if args.verify and summary["verify_all_exact"] is False:
        raise SystemExit("stream replay: answers differ from the static "
                         "session (verify_all_exact is false)")
    return summary


if __name__ == "__main__":
    main()
