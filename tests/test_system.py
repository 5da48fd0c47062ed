"""End-to-end system test: distributed mining (multi-device via subprocess)."""
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow


def test_distributed_mining_multidevice():
    """The engine's sharded TC fold on 8 fake devices == the single-device
    estimate, through ``mine()`` and through the CLI's default path."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys; sys.path.insert(0, %r)
import jax
from repro.core import graph as G
from repro.launch.mine import main, mine
g = G.erdos_renyi(300, 0.05, seed=5)
mesh = jax.make_mesh((4, 2), ("data", "model"))
out = mine(g, mesh, storage_budget=0.5)
print("TC8=", out["tc_estimate"], out["devices"])
cli = main(["--scale", "8", "--budget", "0.5"])
print("CLI=", json.dumps(cli))
"""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", script % src],
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    tc8, ndev = next(ln for ln in lines if ln.startswith("TC8=")).split()[1:]
    assert int(ndev) == 8
    cli = json.loads(next(ln for ln in lines if ln.startswith("CLI="))[4:])
    assert cli["devices"] == 8

    # single-device references with the same sketch params
    from repro.core import graph as G, sketches as S
    from repro.core import triangle_count
    for g, got in ((G.erdos_renyi(300, 0.05, seed=5), float(tc8)),
                   (G.kronecker(8, 16, seed=1), cli["tc_estimate"])):
        sk = S.build(g, "bf", storage_budget=0.5, num_hashes=2, seed=0)
        tc1 = float(triangle_count(g, sk))
        assert abs(got - tc1) / max(tc1, 1) < 1e-3, (got, tc1)
