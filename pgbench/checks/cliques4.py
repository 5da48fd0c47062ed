"""The output check of the 4-clique cell (traffic ``check`` "cliques4"):
what the timed path produced against the plain reference, as named numbers
that the cell's limits bound.

``numbers(traffic, config, outputs, seed)`` regenerates the cell's graph
from the seed with the benchmark's own generator, lists its triangles and
estimates cc4 with ``pgbench/reference/cliques.py``, and returns
``{number: value}``; a larger value is always worse:

- ``edges_differing``, ``sketch_rows_differing``: rows of the program's
  edge list and Bloom rows unequal to the reference's;
- ``triangles_differing``: the symmetric difference of the program's and
  the reference's triangle sets, keyed a·n² + b·n + c in the listed (rank)
  order, plus every triangle the program lists twice;
- ``and_ones_gap``: |program - reference| of Σ popcount(B_a & B_b & B_c)
  over the listed triangles, the integer behind cc4 (the program's from
  the same compiled pass as its cc4), so a dropped, repeated or altered
  popcount shows however the estimates average out;
- ``cc4_rel_gap``: |cc4 - reference| / reference, the reference's
  estimates in float64.

``control_numbers(config, traffic, seed)`` gives the same numbers for the
control: the reference put in the program's place with its per-triangle
estimates computed in bfloat16, the precision below the configuration's
float32, and summed in float32, as a TPU accumulates bfloat16.
``PERF.md`` lists the readings the limits were set from.
"""
from __future__ import annotations

import numpy as np

from pgbench.compare import rel_gap, rows_differing
from pgbench.gen import kronecker as K
from pgbench.reference import cliques as RC
from pgbench.reference import sketch as S


def cliques_reference(config: dict, seed: int) -> dict:
    """The reference job for the cell's graph at ``seed``, with each
    triangle's 3-way AND popcount (``triple_ones``)."""
    n = 1 << config["scale"]
    uv = K.decode(n, K.canonical_keys(n, K.shuffled(config, seed)))
    sk = S.bloom_of_graph(n, uv, config["words"], config["num_hashes"],
                          config["hash_seed"])
    tris, _ = RC.triangles(n, uv)
    ones = RC.triple_and_ones(sk, tris)
    return {"edges": uv, "sketch": sk, "triangles": tris,
            "triple_ones": ones, "ones": int(ones.sum()),
            "cc4": RC.four_clique_estimate(ones, sk.shape[1] * 32,
                                           config["num_hashes"])}


def triangles_differing(n: int, got: np.ndarray, want: np.ndarray) -> int:
    """Triangles in one list and not the other, plus repeats in ``got``."""
    keys = RC.triangle_keys(n, got)
    unique = np.unique(keys)
    return int(keys.size - unique.size
               + np.setxor1d(unique, RC.triangle_keys(n, want)).size)


def compare(config: dict, outputs: dict, ref: dict) -> dict:
    """Compared numbers, the worst over the kept jobs."""
    n = 1 << config["scale"]
    worst = {"edges_differing": rows_differing(outputs["edges"],
                                               ref["edges"])}
    for job in outputs["jobs"].values():
        nums = {
            "sketch_rows_differing": rows_differing(job["sketch"],
                                                    ref["sketch"]),
            "triangles_differing": triangles_differing(
                n, job["triangles"], ref["triangles"]),
            "and_ones_gap": abs(int(job["ones"]) - ref["ones"]),
            "cc4_rel_gap": rel_gap(job["cc4"], ref["cc4"]),
        }
        for k, v in nums.items():
            worst[k] = max(worst.get(k, 0), v)
    return worst


def numbers(traffic: dict, config: dict, outputs: dict, seed: int) -> dict:
    """Compared numbers of the 4-clique cell."""
    return compare(config, outputs, cliques_reference(config, seed))


def control_numbers(config: dict, traffic: dict, seed: int) -> dict:
    """The compared numbers of the bfloat16 control."""
    import ml_dtypes

    ref = cliques_reference(config, seed)
    low = RC.four_clique_estimate(ref["triple_ones"],
                                  ref["sketch"].shape[1] * 32,
                                  config["num_hashes"], ml_dtypes.bfloat16,
                                  np.float32)
    job = {"sketch": ref["sketch"], "triangles": ref["triangles"],
           "ones": ref["ones"], "cc4": low}
    return compare(config, {"edges": ref["edges"],
                            "jobs": {"control": job}}, ref)
