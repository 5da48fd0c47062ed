"""Share of the HBM roofline that the triangle listing reaches: its least
HBM time (``pgbench/cliques_roofline.py``, over peak bandwidth) over the
device-busy time inside the benchmark's span around
``MiningSession.triangles()``, in percent."""
from pgbench import cliques_roofline, roofline


def read(run):
    s = run.shapes
    if run.trace is None or "triangles" not in s:
        return None
    count = run.trace["span_count"].get("pgbench.triangles", 0)
    busy = run.trace["span_busy_s"].get("pgbench.triangles", 0.0)
    least = count * cliques_roofline.triangle_list_min_bytes(
        s["n"], s["m"], s["triangles"])
    return roofline.roofline_pct(least, run.peaks["hbm_bytes_per_s"], busy)
