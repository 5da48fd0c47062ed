"""Share of the HBM roofline that the 3-way Bloom AND over the triangle
list reaches: its least HBM time (unique-row bytes,
``pgbench/cliques_roofline.py``, over peak bandwidth) over the device-busy
time inside the benchmark's span around
``MiningSession.four_clique_count()``, in percent."""
from pgbench import cliques_roofline, roofline


def read(run):
    s = run.shapes
    if run.trace is None or "triangles" not in s:
        return None
    count = run.trace["span_count"].get("pgbench.four_clique_count", 0)
    busy = run.trace["span_busy_s"].get("pgbench.four_clique_count", 0.0)
    least = count * cliques_roofline.triple_and_min_bytes(
        s["n"], s["words"], s["triangles"])
    return roofline.roofline_pct(least, run.peaks["hbm_bytes_per_s"], busy)
