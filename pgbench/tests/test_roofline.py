"""The peak table and the card pass's byte count."""
import json
import subprocess
import sys

import small
from pgbench import harness, roofline


def test_peaks_table_has_v5e_and_refuses_others():
    peaks = json.loads((harness.BENCH_DIR / "peaks.json").read_text())
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert "TPU v5" in peaks["source"] or "v5e" in peaks["source"]


def test_card_pass_bytes_same_for_kernel_and_jnp_plans():
    from repro import engine as ENG

    cell = small.small_cell("mine.build_query", 9)
    from pgbench.gen import kronecker as K
    from repro.core import graph as G

    n = 1 << cell.config["scale"]
    g = G.from_edge_array(n, K.shuffled(cell.config, 3))
    counted = set()
    for use_kernel in (False, True):
        sess = ENG.session(g, "bf", storage_budget=4.0, use_kernel=use_kernel)
        assert sess.plan.use_kernel is use_kernel
        counted.add(roofline.card_pass_min_bytes(
            sess.graph.n, sess.sketch.data.shape[1], sess.graph.m))
    assert len(counted) == 1
    (least,) = counted
    assert least == n * sess.sketch.data.shape[1] * 4 + g.m * 12
    assert roofline.card_pass_gathered_bytes(116, 10) == 10 * 2 * 116 * 4
    assert roofline.roofline_pct(819, 819e9, 2e-9) == 50.0
    assert roofline.roofline_pct(819, 819e9, 0.0) is None


def test_no_tpu_exits_nonzero_without_a_result():
    proc = subprocess.run(
        [sys.executable, "pgbench/run.py", "--workload", "mine.query",
         "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=small.ROOT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
