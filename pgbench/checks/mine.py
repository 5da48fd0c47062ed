"""The output check of the mining cells (traffic ``check`` "mine"): what
the timed path produced against the plain reference, as named numbers that
the cell's limits bound.

``numbers(traffic, config, outputs, seed)`` regenerates the cell's graph
from the seed with the benchmark's own generator, runs the reference in
``pgbench/reference`` and returns ``{number: value}``; a larger value is
always worse. The numbers are listed in ``PERF.md`` with the readings their
limits were set from.
"""
from __future__ import annotations

import numpy as np

from pgbench.compare import rel_gap, rows_differing
from pgbench.gen import kronecker as K
from pgbench.reference import mining as RM


def mine_reference(config: dict, traffic: dict, seed: int,
                   dtype=np.float64) -> dict:
    """The reference job for the cell's graph at ``seed``."""
    n = 1 << config["scale"]
    keys = K.canonical_keys(n, K.shuffled(config, seed))
    uv = K.decode(n, keys)
    ref = RM.job(n, uv, config["words"], config["num_hashes"],
                 config["hash_seed"], jp_threshold(traffic), dtype=dtype)
    ref["edges"] = uv
    return ref


def jp_threshold(traffic: dict) -> float:
    """The Jarvis-Patrick threshold of the traffic's jaccard query."""
    (query,) = [q for q in traffic["queries"] if q[0] == "jarvis_patrick"]
    if query[1] != "jaccard":
        raise ValueError(f"the reference scores Jarvis-Patrick by jaccard "
                         f"only, not {query[1]!r}")
    return float(query[2])


def jp_differing(ref: dict, labels: np.ndarray) -> int:
    """Vertices whose Jarvis-Patrick label no reference outcome gives."""
    n = ref["lcc"].shape[0]
    if labels.shape != (n,):
        return n
    want = RM.jp_labels_accepted(n, ref["edges"], ref["jp_sure"],
                                 ref["jp_ambiguous"], labels.astype(np.int64))
    return int(np.sum(labels != want))


def numbers(traffic: dict, config: dict, outputs: dict, seed: int) -> dict:
    """Compared numbers of a mining cell, the worst over the kept jobs."""
    ref = mine_reference(config, traffic, seed)
    worst = {"edges_differing": rows_differing(outputs["edges"],
                                               ref["edges"])}
    for job in outputs["jobs"].values():
        nums = {
            "sketch_rows_differing": rows_differing(job["sketch"],
                                                    ref["sketch"]),
            # |got - want| / max(|want|, 1) over every estimate the job
            # gives: per-edge cards, the TC sum and the LCC values
            "estimate_max_gap": max(rel_gap(job["cards"], ref["cards"]),
                                    rel_gap(job["tc"], ref["tc"]),
                                    rel_gap(job["lcc"], ref["lcc"])),
            "jp_labels_differing": jp_differing(ref, job["jp_labels"]),
        }
        for k, v in nums.items():
            worst[k] = max(worst.get(k, 0), v)
    return worst
