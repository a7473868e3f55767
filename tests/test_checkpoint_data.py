"""Checkpoint roundtrip/GC/async + token-pipeline determinism tests."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.checkpoint import (
    CheckpointManager,
    latest_step,
    load_checkpoint,
    save_checkpoint,
)
from repro.data.tokens import TokenPipeline


def _tree():
    return {
        "params": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4)},
        "opt": {"m": jnp.ones((3, 4)), "step": jnp.asarray(7, jnp.int32)},
    }


def test_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 5, t)
    assert latest_step(str(tmp_path)) == 5
    got, manifest = load_checkpoint(str(tmp_path), 5, t)
    assert manifest["step"] == 5
    np.testing.assert_array_equal(
        np.asarray(got["params"]["w"]), np.asarray(t["params"]["w"])
    )
    assert int(got["opt"]["step"]) == 7


def test_structure_mismatch_rejected(tmp_path):
    save_checkpoint(str(tmp_path), 1, _tree())
    bad = {"params": {"w2": jnp.zeros((3, 4))}}
    with pytest.raises(ValueError, match="structure mismatch"):
        load_checkpoint(str(tmp_path), 1, bad)


def test_shape_mismatch_rejected(tmp_path):
    save_checkpoint(str(tmp_path), 1, _tree())
    bad = _tree()
    bad["params"]["w"] = jnp.zeros((4, 4))
    with pytest.raises(ValueError, match="shape mismatch"):
        load_checkpoint(str(tmp_path), 1, bad)


def test_manager_async_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), every=2, gc_keep=2)
    t = _tree()
    for step in range(1, 9):
        mgr.maybe_save(step, t)
    mgr.wait()
    steps = sorted(
        int(n.split("_")[1]) for n in os.listdir(tmp_path) if n.startswith("step_")
    )
    assert len(steps) <= 2  # gc kept the last two
    assert latest_step(str(tmp_path)) == 8


def test_elastic_restore_resharding(tmp_path):
    """Save replicated, restore with an explicit (1-device) sharding."""
    t = _tree()
    save_checkpoint(str(tmp_path), 3, t)
    mesh = make_mesh((1,), ("data",))
    sh = jax.tree.map(
        lambda _: jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()), t
    )
    got, _ = load_checkpoint(str(tmp_path), 3, t, shardings=sh)
    np.testing.assert_array_equal(
        np.asarray(got["params"]["w"]), np.asarray(t["params"]["w"])
    )


def test_token_pipeline_deterministic():
    p1 = TokenPipeline(1000, 32, 4, seed=7)
    p2 = TokenPipeline(1000, 32, 4, seed=7)
    b1, b2 = p1.batch(13), p2.batch(13)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    np.testing.assert_array_equal(b1["labels"], b2["labels"])
    b3 = p1.batch(14)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_token_pipeline_host_sharding():
    """Different hosts must produce disjoint streams; together they tile the
    global batch deterministically."""
    g = TokenPipeline(1000, 16, 8, seed=3, num_hosts=2, host_id=0)
    h = TokenPipeline(1000, 16, 8, seed=3, num_hosts=2, host_id=1)
    assert g.local_batch == 4 and h.local_batch == 4
    bg, bh = g.batch(0), h.batch(0)
    assert not np.array_equal(bg["tokens"], bh["tokens"])


def test_token_pipeline_learnable_structure():
    """The Markov overlay must make labels partially predictable."""
    p = TokenPipeline(100, 512, 2, seed=0)
    b = p.batch(0)
    follow = (b["tokens"] * 31 + 7) % 100
    frac = float(np.mean(follow == b["labels"]))
    assert frac > 0.25  # q=0.35 minus collisions


def test_token_pipeline_prefetch_iterator():
    p = TokenPipeline(100, 8, 2, seed=0)
    it = p.iterator(start_step=0, prefetch=2)
    b0 = next(it)
    np.testing.assert_array_equal(b0["tokens"], p.batch(0)["tokens"])
    b1 = next(it)
    np.testing.assert_array_equal(b1["tokens"], p.batch(1)["tokens"])
