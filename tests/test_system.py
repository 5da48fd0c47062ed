"""End-to-end system tests: training driver, fault recovery, serving,
distributed mining (multi-device via subprocess)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.slow

from repro.distributed.fault import FaultInjector, StepMonitor
from repro.launch.train import TrainRunConfig, train


def test_train_loop_loss_decreases(tmp_path):
    run = TrainRunConfig(arch="gemma_2b", steps=25, global_batch=8,
                         seq_len=32, d_model=64, layers=2, lr=5e-3,
                         vocab_size=128)
    _, hist = train(run)
    first = np.mean([h["loss"] for h in hist[:5]])
    last = np.mean([h["loss"] for h in hist[-5:]])
    assert last < first, (first, last)


def test_train_recovers_from_injected_fault(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    run = TrainRunConfig(arch="qwen3_8b", steps=24, global_batch=4,
                         seq_len=32, d_model=64, layers=2, vocab_size=128,
                         ckpt_dir=ckpt, ckpt_every=6)
    fault = FaultInjector(fail_at_steps=[13])
    _, hist = train(run, fault=fault)
    steps_seen = [h["step"] for h in hist]
    assert 13 in fault.fired
    # restarted from step-12 checkpoint and completed
    assert steps_seen.count(12) >= 1
    assert steps_seen[-1] == 23


def test_train_resume_is_deterministic(tmp_path):
    """Same data at step k whether run straight or resumed (elastic restart)."""
    ckpt = str(tmp_path / "ckpt")
    base = dict(arch="gemma_2b", steps=12, global_batch=4, seq_len=32,
                d_model=64, layers=2, vocab_size=128, ckpt_every=6)
    _, h1 = train(TrainRunConfig(**base, ckpt_dir=ckpt))
    # rerun with a fault right after the step-6 checkpoint
    ckpt2 = str(tmp_path / "ckpt2")
    fault = FaultInjector(fail_at_steps=[7])
    _, h2 = train(TrainRunConfig(**base, ckpt_dir=ckpt2), fault=fault)
    l1 = {h["step"]: h["loss"] for h in h1}
    l2 = {h["step"]: h["loss"] for h in h2}
    for s in (8, 9, 10, 11):
        assert abs(l1[s] - l2[s]) < 1e-4, (s, l1[s], l2[s])


def test_compressed_grads_trains(tmp_path):
    run = TrainRunConfig(arch="gemma_2b", steps=15, global_batch=4,
                         seq_len=32, d_model=64, layers=2, vocab_size=128,
                         compress_grads=True)
    _, hist = train(run)
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_adafactor_driver(tmp_path):
    """Adafactor path through the driver descends (slower than AdamW by
    design — decaying beta2 + update clipping need more steps)."""
    run = TrainRunConfig(arch="gemma_2b", steps=100, global_batch=8,
                         seq_len=32, d_model=64, layers=2, vocab_size=128,
                         optimizer="adafactor", lr=3e-2, warmup=10)
    _, hist = train(run)
    assert np.mean([h["loss"] for h in hist[-10:]]) < hist[0]["loss"] - 0.05


def test_step_monitor_flags_stragglers():
    mon = StepMonitor(window=16, straggler_factor=2.0)
    for i in range(10):
        mon.record(i, 0.1)
    assert mon.record(10, 0.5)
    assert not mon.record(11, 0.12)


def test_serving_generates():
    from repro.launch.serve import BatchedServer, ServeConfig
    server = BatchedServer(ServeConfig(arch="gemma_2b", batch=2, max_len=64,
                                       d_model=64, layers=2))
    out = server.generate([[1, 2, 3], [4, 5]], num_tokens=8, greedy=True)
    assert out.shape == (2, 8)
    assert (out >= 0).all() and (out < server.cfg.vocab_size).all()


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
