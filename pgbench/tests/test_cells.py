"""Every cell's files load by name, and each cell's run on the CPU at a
small size is judged correct against the plain reference."""
import json

import numpy as np
import pytest

import small
from pgbench import harness

BENCH = json.loads((small.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_load_by_name(name):
    cell = harness.Cell(name)
    assert cell.loop().Loop
    for m in cell.end_to_end + cell.per_layer:
        assert (harness.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
    assert cell.config["name"] == cell.entry["config"]
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)


@pytest.mark.parametrize("name", CELLS)
def test_cell_correct_on_cpu(name):
    cell = small.small_cell(name, 10)
    out = small.run_small(cell, seed=2**31 + 17, seconds=1.0)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_same_seed_same_inputs(name):
    from pgbench.gen import kronecker as K

    cell = small.small_cell(name, 9)
    a, b = K.shuffled(cell.config, 2**31 + 3), K.shuffled(cell.config,
                                                           2**31 + 3)
    c = K.shuffled(cell.config, 2**31 + 4)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    n = 1 << cell.config["scale"]
    assert np.array_equal(np.sort(np.bincount(a.ravel(), minlength=n)),
                          np.sort(np.bincount(c.ravel(), minlength=n)))
