"""Batch 4-clique counting jobs over one Graph500 graph, as
``launch/mine.py --algos 4clique`` runs them.

Set-up is the mining loop's (``pgbench/loops/mine_job.py``): the graph from
the seed's shuffled edge array and the Bloom sketch built once. A job is a
fresh ``repro.engine.session(graph, sketch)``, then its triangle list
(``MiningSession.triangles()``) and the 4-clique count over that list
(``MiningSession.four_clique_count()``, which also returns the Σ of the
3-way AND popcounts behind the count), each in its own span and ending in
``block_until_ready``. After the warm job the run's shapes gain the
triangle count T the program found. The outputs of the first job and of one
more drawn from the seed are kept for the check.
"""
from __future__ import annotations

import jax
import numpy as np

from pgbench.loops import mine_job


class Loop(mine_job.Loop):
    def _job(self) -> dict:
        run = self.run
        with run.span("pgbench.job"):
            sess = self.eng.session(self.graph, self.sketch)
            with run.span("pgbench.triangles"):
                tris, count = sess.triangles()
                jax.block_until_ready(tris)
            with run.span("pgbench.four_clique_count"):
                cc4, ones = jax.block_until_ready(
                    sess.four_clique_count(return_ones=True))
        return {"sketch": self.sketch.data, "triangles": tris,
                "count": count, "cc4": cc4, "ones": ones}

    def warm(self) -> None:
        self.run.shapes["triangles"] = self._job()["count"]

    def outputs(self) -> dict:
        def ones(words):
            hi, lo = np.asarray(words).tolist()
            return hi << 32 | lo

        host = {tag: {"sketch": np.asarray(out["sketch"]),
                      "triangles": np.asarray(out["triangles"])[:out["count"]],
                      "cc4": float(out["cc4"]), "ones": ones(out["ones"])}
                for tag, out in self.kept.items()}
        return {"edges": np.asarray(self.graph.edges), "jobs": host}
