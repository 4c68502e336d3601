"""Checkpoint manager: atomic commit, keep-k, async, elastic restore."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core.lns import LNSFormat
from repro.launch.mesh import make_mesh
from repro.optim.madam import LNSWeight

_FMT = LNSFormat(bits=16, gamma=8 * (1 << 8))


def _state(key, scale=1.0):
    return {
        "w": LNSWeight(packed=(jnp.arange(16).reshape(4, 4) * scale
                               ).astype(jnp.uint16),
                       scale=jnp.ones((1, 4)), fmt=_FMT),
        "b": jax.random.normal(key, (8,)),
        "step": jnp.asarray(7, jnp.int32),
    }


def test_save_restore_roundtrip(tmp_path, key):
    m = CheckpointManager(str(tmp_path), keep=3)
    st = _state(key)
    m.save(7, st, data_cursor=42, async_=False)
    assert m.latest_step() == 7
    assert m.manifest(7)["data_cursor"] == 42
    step, restored = m.restore_latest(st)
    assert step == 7
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype


def test_async_save_and_wait(tmp_path, key):
    m = CheckpointManager(str(tmp_path))
    m.save(1, _state(key), async_=True)
    m.wait()
    assert m.latest_step() == 1


def test_keep_k_gc(tmp_path, key):
    m = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        m.save(s, _state(key), async_=False)
    dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert dirs == ["step_00000003", "step_00000004"]
    assert m.latest_step() == 4


def test_atomicity_no_partial_latest(tmp_path, key):
    """LATEST only ever points at a fully-committed snapshot."""
    m = CheckpointManager(str(tmp_path))
    m.save(5, _state(key), async_=False)
    # simulate a crashed later save: orphaned tmp dir
    os.makedirs(os.path.join(tmp_path, "step_00000009.tmp"))
    assert m.latest_step() == 5
    _, restored = m.restore_latest(_state(key))
    assert int(restored["step"]) == 7  # payload intact


def test_elastic_restore_with_shardings(tmp_path, key):
    """Restore places arrays with explicitly-provided (new-mesh) shardings."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh((1,), ("data",))
    m = CheckpointManager(str(tmp_path))
    st = {"w": jnp.arange(8.0)}
    m.save(1, st, async_=False)
    sh = {"w": NamedSharding(mesh, P("data"))}
    _, restored = m.restore_latest(st, shardings=sh)
    assert restored["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(st["w"]))


def test_restore_casts_dtype(tmp_path, key):
    m = CheckpointManager(str(tmp_path))
    st = {"w": jnp.arange(8, dtype=jnp.float32)}
    m.save(1, st, async_=False)
    like = {"w": jnp.zeros(8, jnp.bfloat16)}
    _, restored = m.restore_latest(like)
    assert restored["w"].dtype == jnp.bfloat16
