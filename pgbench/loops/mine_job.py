"""Batch mining jobs over one Graph500 graph, as ``launch/mine.py`` runs them.

A job is the traffic's ``queries`` over one engine session: the sketch build
(``repro.engine.session(graph, "bf", storage_budget=...)``) when
``build_in_job`` is set, else a fresh session on the sketch built once in
set-up, then the shared card pass and each query, each ending in
``block_until_ready``. Every job runs the same inputs; the outputs of the
first job and of one more drawn from the seed are kept for the check.
"""
from __future__ import annotations

import jax
import numpy as np

from pgbench.gen import kronecker as K


class Loop:
    def __init__(self, config: dict, traffic: dict, seed: int, run):
        from repro import engine as ENG
        from repro.core import graph as G

        self.cfg, self.traffic, self.run = config, traffic, run
        self.eng = ENG
        n = 1 << config["scale"]
        with run.span("pgbench.graph_build"):
            edges = K.shuffled(config, seed)
            self.graph = G.from_edge_array(
                n, edges, pad_to_max_degree=config["adj_width"])
            jax.block_until_ready(self.graph.adj)
        if (self.graph.d_max != config["adj_width"]
                or self.graph.m != config["m"]):
            raise RuntimeError(
                f"graph (m={self.graph.m}, d_max={self.graph.d_max}) does not "
                f"fit the configuration's pads (m={config['m']}, "
                f"adj_width={config['adj_width']})")
        self.sketch = None
        if not traffic["build_in_job"]:
            with run.span("pgbench.sketch_build"):
                self.sketch = self._build().sketch
                jax.block_until_ready(self.sketch.data)
        self.rng = K.rng_for(seed, 2)
        self.jobs = 0
        self.kept = {}
        run.shapes = {"n": n, "m": self.graph.m, "words": config["words"]}

    def _build(self):
        sess = self.eng.session(
            self.graph, "bf", storage_budget=self.cfg["storage_budget"],
            num_hashes=self.cfg["num_hashes"], seed=self.cfg["hash_seed"])
        if sess.sketch.data.shape[1] != self.cfg["words"]:
            raise RuntimeError(f"sketch width {sess.sketch.data.shape[1]} is "
                               f"not the configuration's {self.cfg['words']}")
        return sess

    def _job(self) -> dict:
        run = self.run
        with run.span("pgbench.job"):
            if self.sketch is None:
                with run.span("pgbench.sketch_build"):
                    sess = self._build()
                    jax.block_until_ready(sess.sketch.data)
            else:
                sess = self.eng.session(self.graph, self.sketch)
            out = {"sketch": sess.sketch.data}
            with run.span("pgbench.edge_cards"):
                out["cards"] = jax.block_until_ready(sess.edge_cardinalities())
            for query in self.traffic["queries"]:
                name, args = query[0], query[1:]
                with run.span(f"pgbench.{name}"):
                    out[name] = jax.block_until_ready(
                        getattr(sess, name)(*args))
        return out

    def warm(self) -> None:
        self._job()

    def step(self) -> None:
        out = self._job()
        self.jobs += 1
        self.run.counters["jobs"] = self.jobs
        # keep the first job and one more drawn from the seed (reservoir)
        if self.jobs == 1:
            self.kept["first"] = out
        elif self.rng.random() < 1.0 / (self.jobs - 1):
            self.kept["drawn"] = out

    def counts(self):
        return self.jobs, 0

    def outputs(self) -> dict:
        host = {}
        for tag, out in self.kept.items():
            labels, clusters = out["jarvis_patrick"]
            host[tag] = {"sketch": np.asarray(out["sketch"]),
                         "cards": np.asarray(out["cards"]),
                         "tc": float(out["triangle_count"]),
                         "lcc": np.asarray(out["local_clustering"]),
                         "jp_labels": np.asarray(labels),
                         "jp_clusters": int(clusters)}
        return {"edges": np.asarray(self.graph.edges), "jobs": host}
