"""Where the entry points put JAX's persistent compilation cache.

Each case runs in a child process on the CPU, so the test process's own JAX
configuration stays untouched.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src"

CHILD = r"""
import json, os, sys
sys.path.insert(0, %r)
import jax, jax.numpy as jnp
from repro.compile_cache import CHECKOUT_CACHE, use_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
chosen = use_compile_cache()
if %r:
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(11.0)).block_until_ready()
print(json.dumps({"chosen": chosen, "config": jax.config.jax_compilation_cache_dir,
                  "checkout": str(CHECKOUT_CACHE)}))
"""


def _run_child(env_dir, compile_one: bool) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c", CHILD % (str(SRC), compile_one)],
                          capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_cache_dir_from_environment_wins(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, entries land there and the
    helper sets no other directory."""
    cache = tmp_path / "jax-cache"
    out = _run_child(cache, compile_one=True)
    assert out["chosen"] == out["config"] == str(cache)
    assert any(cache.iterdir())


def test_cache_dir_defaults_to_checkout():
    """Without the variable, the cache is the fixed ``.jax_cache`` at the
    root of the checkout."""
    out = _run_child(None, compile_one=False)
    root = SRC.parent
    assert out["chosen"] == out["config"] == out["checkout"]
    assert Path(out["checkout"]) == root / ".jax_cache"


@pytest.mark.parametrize("module", ["repro", "repro.engine", "repro.launch.mine"])
def test_import_sets_no_cache(module):
    """Importing the library or an entry point's module places no cache."""
    code = (f"import sys; sys.path.insert(0, {str(SRC)!r}); import jax, json, {module}; "
            "print(json.dumps(jax.config.jax_compilation_cache_dir))")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) is None
