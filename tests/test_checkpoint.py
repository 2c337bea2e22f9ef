"""Checkpoint/restore: round trips, atomicity, elastic re-sharding."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.checkpoint import snapshot
from repro.train import optimizer as opt


def _tree(key):
    k1, k2 = jax.random.split(key)
    return {"w": jax.random.normal(k1, (8, 16), jnp.bfloat16),
            "b": jax.random.normal(k2, (16,), jnp.float32),
            "nested": {"u0": jnp.arange(12, dtype=jnp.int32)}}


def test_roundtrip_with_opt_state(tmp_path):
    params = _tree(jax.random.PRNGKey(0))
    ostate = opt.init(params)
    snapshot.save(str(tmp_path), params, ostate, step=42,
                  commit_vector=[3, 1, 4])
    p2, o2, meta = snapshot.restore(str(tmp_path), params, ostate)
    assert meta["step"] == 42
    assert meta["commit_vector"] == [3, 1, 4]
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert int(o2.step) == int(ostate.step)


def test_manifest_commit_is_atomic(tmp_path):
    """A crash mid-save must never leave a readable-but-partial manifest:
    the manifest is written last via os.replace."""
    params = _tree(jax.random.PRNGKey(1))
    snapshot.save(str(tmp_path), params, step=1)
    assert os.path.exists(tmp_path / "manifest.json")
    assert not os.path.exists(tmp_path / "manifest.json.tmp")
    man = json.load(open(tmp_path / "manifest.json"))
    # every referenced leaf file exists (manifest implies completeness)
    for leaf in man["leaves"].values():
        assert os.path.exists(tmp_path / leaf["file"])


def test_save_async_joins_and_matches(tmp_path):
    params = _tree(jax.random.PRNGKey(2))
    t = snapshot.save_async(str(tmp_path), params, step=7)
    t.join()
    p2, _, meta = snapshot.restore(str(tmp_path), params)
    assert meta["step"] == 7
    np.testing.assert_array_equal(
        np.asarray(params["w"], np.float32), np.asarray(p2["w"], np.float32))


def test_elastic_restore_new_sharding(tmp_path):
    """A checkpoint written under one topology re-lands under another —
    here: saved unsharded, restored with explicit single-device
    NamedShardings (the mesh-shape-agnostic path)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    params = _tree(jax.random.PRNGKey(3))
    snapshot.save(str(tmp_path), params, step=3)
    mesh = compat.mesh(1, "data")
    shard_tree = jax.tree.map(
        lambda x: NamedSharding(mesh, P(*([None] * x.ndim))), params)
    p2, _, _ = snapshot.restore(str(tmp_path), params,
                                shardings={"params": shard_tree})
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
        assert isinstance(jax.tree.leaves(p2)[0].sharding, NamedSharding)
