"""Checkpoint tests: round trip, async writes, retention,
crash-consistency and elastic restore."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "params": {"w": jnp.asarray(rng.standard_normal((4, 8)), jnp.float32),
                   "b": jnp.asarray(rng.standard_normal(8), jnp.float32)},
        "step": jnp.int32(7),
    }


def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path))
    tree = _tree()
    ck.save(3, tree, blocking=True)
    got = ck.restore(jax.tree.map(jnp.zeros_like, tree))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_async_then_wait(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, _tree(1))           # async
    ck.wait()
    assert ck.available_steps() == [1]


def test_checkpoint_keeps_latest_k(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, _tree(s), blocking=True)
    assert ck.available_steps() == [3, 4]


def test_checkpoint_uncommitted_is_invisible(tmp_path):
    """A partially-written checkpoint (no COMMITTED marker) is skipped —
    crash consistency for preempted writers."""
    ck = Checkpointer(str(tmp_path))
    ck.save(5, _tree(), blocking=True)
    # simulate a torn write at a later step
    torn = os.path.join(str(tmp_path), "step_00000009")
    os.makedirs(torn)
    with open(os.path.join(torn, "manifest.json"), "w") as f:
        f.write("{}")
    assert ck.latest_step() == 5
    got = ck.restore(jax.tree.map(jnp.zeros_like, _tree()))
    assert int(got["step"]) == 7


def test_checkpoint_restore_specific_step(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=5)
    ck.save(1, _tree(1), blocking=True)
    ck.save(2, _tree(2), blocking=True)
    got1 = ck.restore(jax.tree.map(jnp.zeros_like, _tree()), step=1)
    want1 = _tree(1)
    np.testing.assert_array_equal(
        np.asarray(got1["params"]["w"]), np.asarray(want1["params"]["w"])
    )


def test_checkpoint_elastic_resharding(tmp_path):
    """Restore with explicit shardings — the elastic-scale path (write on
    mesh A, restore to mesh B = here, 1-device mesh with new layout)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    ck = Checkpointer(str(tmp_path))
    tree = _tree()
    ck.save(1, tree, blocking=True)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    sh = {
        "params": {
            "w": NamedSharding(mesh, P("data", None)),
            "b": NamedSharding(mesh, P()),
        },
        "step": NamedSharding(mesh, P()),
    }
    got = ck.restore(jax.tree.map(jnp.zeros_like, tree), shardings=sh)
    assert got["params"]["w"].sharding == sh["params"]["w"]
    np.testing.assert_array_equal(
        np.asarray(got["params"]["w"]), np.asarray(tree["params"]["w"])
    )
