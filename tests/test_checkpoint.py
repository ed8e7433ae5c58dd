"""Checkpoint store tests: atomicity, keep-k, async, bf16 round-trip,
elastic restore, and the resume protocol."""
import os
import shutil

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager, latest_step, restore, save
from repro.checkpoint import store as S


@pytest.fixture()
def tree():
    return {
        "a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
        "nested": {"b": jnp.ones((2, 2), jnp.bfloat16) * 1.5,
                   "c": jnp.asarray(7, jnp.int32)},
    }


def test_save_restore_roundtrip(tmp_path, tree):
    d = str(tmp_path)
    save(d, 5, tree, metadata={"step": 5})
    out, meta = restore(d, 5, jax.eval_shape(lambda: tree))
    assert meta == {"step": 5}
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_bfloat16_no_pickle(tmp_path, tree):
    d = str(tmp_path)
    save(d, 1, tree)
    for f in os.listdir(os.path.join(d, "step_00000001")):
        if f.endswith(".npy"):
            arr = np.load(os.path.join(d, "step_00000001", f),
                          allow_pickle=False)   # must not need pickle
            assert arr.dtype == np.uint8


def test_atomic_publish_ignores_partial(tmp_path, tree):
    d = str(tmp_path)
    save(d, 1, tree)
    # simulate a crash mid-write at step 2: tmp dir exists, no manifest
    os.makedirs(os.path.join(d, "step_00000002.tmp"))
    # and a torn final dir without manifest
    os.makedirs(os.path.join(d, "step_00000003"))
    assert latest_step(d) == 1


def test_keep_k_gc(tmp_path, tree):
    d = str(tmp_path)
    mgr = CheckpointManager(d, keep=2, async_write=False)
    for step in (1, 2, 3, 4):
        mgr.save(step, tree)
    assert S.steps(d) == [3, 4]


def test_async_save_and_wait(tmp_path, tree):
    d = str(tmp_path)
    mgr = CheckpointManager(d, keep=3, async_write=True)
    mgr.save(10, tree)
    mgr.wait()
    assert mgr.latest_step() == 10
    out, _ = mgr.restore(10, jax.eval_shape(lambda: tree))
    np.testing.assert_array_equal(np.asarray(out["a"]),
                                  np.asarray(tree["a"]))


def test_restore_rejects_structure_change(tmp_path, tree):
    d = str(tmp_path)
    save(d, 1, tree)
    bad = dict(tree)
    bad["extra"] = jnp.zeros((1,))
    with pytest.raises(ValueError, match="structure changed"):
        restore(d, 1, jax.eval_shape(lambda: bad))


def test_restore_rejects_shape_change(tmp_path, tree):
    d = str(tmp_path)
    save(d, 1, tree)
    bad = dict(tree)
    bad["a"] = jnp.zeros((4, 4))
    with pytest.raises(ValueError, match="shape"):
        restore(d, 1, jax.eval_shape(lambda: bad))


def test_elastic_restore_with_shardings(tmp_path, tree):
    """Restore onto explicit shardings for the *current* mesh (here 1
    device, but the code path is the elastic one)."""
    d = str(tmp_path)
    save(d, 1, tree)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sh = jax.tree.map(
        lambda x: jax.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        jax.eval_shape(lambda: tree))
    out, _ = restore(d, 1, jax.eval_shape(lambda: tree), shardings=sh)
    assert out["a"].sharding.mesh.shape == {"data": 1}


def test_resume_or_init(tmp_path, tree):
    from repro.runtime import fault_tolerance as ft
    d = str(tmp_path)
    mgr = CheckpointManager(d, async_write=False)
    state, start = ft.resume_or_init(mgr, lambda: tree,
                                     jax.eval_shape(lambda: tree))
    assert start == 0                       # fresh init
    mgr.save(7, tree, metadata={"step": 7})
    state, start = ft.resume_or_init(mgr, lambda: tree,
                                     jax.eval_shape(lambda: tree))
    assert start == 7
