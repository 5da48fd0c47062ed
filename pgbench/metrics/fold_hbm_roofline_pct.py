"""Share of the HBM roofline that the shared card pass reaches: the pass's
least HBM time (unique-row bytes over peak bandwidth, see
``pgbench/roofline.py``) over the device-busy time inside the benchmark's
span around ``MiningSession.edge_cardinalities()``, in percent."""
from pgbench import roofline


def read(run):
    if run.trace is None:
        return None
    count = run.trace["span_count"].get("pgbench.edge_cards", 0)
    busy = run.trace["span_busy_s"].get("pgbench.edge_cards", 0.0)
    s = run.shapes
    least = count * roofline.card_pass_min_bytes(s["n"], s["words"], s["m"])
    return roofline.roofline_pct(least, run.peaks["hbm_bytes_per_s"], busy)
