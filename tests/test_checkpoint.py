"""Checkpoint store: atomic publish, GC, async, restore-into-structure."""
import os

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint import (save_checkpoint, restore_checkpoint, latest_step,
                              AsyncCheckpointer)
from repro.checkpoint.store import load_meta


def _tree():
    return {"params": {"w": jnp.arange(12.0).reshape(3, 4),
                       "blocks": [{"a": jnp.ones(5)}, {"a": jnp.zeros(2)}]},
            "step": jnp.asarray(7)}


def test_save_restore_roundtrip(tmp_path):
    d = str(tmp_path / "ckpt")
    tree = _tree()
    save_checkpoint(d, 10, tree)
    assert latest_step(d) == 10
    restored = restore_checkpoint(d, 10, jax.tree.map(np.asarray, tree))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gc_keeps_most_recent(tmp_path):
    d = str(tmp_path / "ckpt")
    for s in range(6):
        save_checkpoint(d, s, _tree(), keep=3)
    names = sorted(os.listdir(d))
    assert names == ["step_00000003", "step_00000004", "step_00000005"]


def test_restore_respects_target_dtype(tmp_path):
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 1, {"w": jnp.ones(4, jnp.float32)})
    target = {"w": jax.ShapeDtypeStruct((4,), jnp.bfloat16)}
    out = restore_checkpoint(d, 1, target)
    assert out["w"].dtype == jnp.bfloat16


def test_async_checkpointer(tmp_path):
    d = str(tmp_path / "ckpt")
    ck = AsyncCheckpointer(d, keep=2)
    for s in (1, 2, 3):
        ck.save(s, _tree())
    ck.wait()
    assert latest_step(d) == 3


def test_crash_mid_save_leaves_previous_intact(tmp_path):
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 1, _tree())
    # simulate a torn write: tmp dir exists but was never renamed
    os.makedirs(os.path.join(d, "step_00000002.tmp.999"), exist_ok=True)
    assert latest_step(d) == 1  # tmp dirs are invisible to discovery


def test_elastic_restore_with_new_sharding(tmp_path):
    """Restore with explicit (single-device) shardings — the elastic path."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",))
    d = str(tmp_path / "ckpt")
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    save_checkpoint(d, 5, tree)
    shardings = {"w": NamedSharding(mesh, P("data", None))}
    out = restore_checkpoint(d, 5, tree, shardings)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
    assert out["w"].sharding == shardings["w"]


def test_load_meta_reads_shapes_without_arrays(tmp_path):
    """``load_meta`` gives every saved leaf's shape and dtype from
    meta.json alone: it still answers once the array files are gone."""
    d = str(tmp_path / "ckpt")
    path = save_checkpoint(d, 3, _tree())
    for name in os.listdir(path):
        if name.endswith(".npy"):
            os.remove(os.path.join(path, name))
    meta = load_meta(d, 3)
    assert meta["step"] == 3
    want = {"params__w": ([3, 4], "float32"),
            "params__blocks__idx0__a": ([5], "float32"),
            "params__blocks__idx1__a": ([2], "float32"),
            "step": ([], "int32")}
    assert {key: (leaf["shape"], leaf["dtype"])
            for key, leaf in meta["leaves"].items()} == want

