"""Checkpointing: roundtrip exactness, async, resharded restore, driver
restart/rescale recovery."""
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import run_with_devices

from repro.checkpoint.store import CheckpointError, CheckpointStore
from repro.runtime.driver import HeartbeatMonitor, TrainDriver

key = jax.random.PRNGKey(0)


def make_tree():
    return {
        "w": jax.random.normal(key, (64, 32), jnp.float32),
        "emb": {"table": jax.random.normal(key, (100, 16)).astype(jnp.bfloat16)},
        "step": jnp.asarray(7, jnp.int32),
    }


def test_roundtrip_exact(tmp_path):
    store = CheckpointStore(str(tmp_path))
    tree = make_tree()
    store.save(3, tree, n_shards=4)
    back = store.restore(tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))


def test_latest_and_multiple_steps(tmp_path):
    store = CheckpointStore(str(tmp_path))
    t = make_tree()
    store.save(1, t)
    store.save(5, t)
    assert store.latest_step() == 5


def test_async_save(tmp_path):
    store = CheckpointStore(str(tmp_path))
    t = make_tree()
    store.save_async(9, t)
    store.wait()
    back = store.restore(t)
    assert np.array_equal(np.asarray(back["w"]), np.asarray(t["w"]))


def test_driver_restart_from_failure(tmp_path):
    """Node failure at step 7 -> restart resumes from checkpoint 5 and still
    reaches the target step count."""
    store = CheckpointStore(str(tmp_path))

    def build_step(mesh_spec):
        state = {"x": jnp.zeros(()), "step": jnp.zeros((), jnp.int32)}

        def step_fn(s):
            s = {"x": s["x"] + 1.0, "step": s["step"] + 1}
            return s, {"loss": 1.0 / (1.0 + float(s["x"]))}
        return step_fn, state

    driver = TrainDriver(store, build_step, checkpoint_every=5,
                         failure_schedule={7: "fail"})
    report = driver.run(total_steps=10, mesh_spec={})
    assert report.restarts == 1
    assert report.checkpoints[-1] == 10
    final = store.restore({"x": jnp.zeros(()), "step": jnp.zeros((), jnp.int32)})
    assert float(final["x"]) == 10.0


def test_driver_elastic_rescale(tmp_path):
    store = CheckpointStore(str(tmp_path))
    seen_meshes = []

    def build_step(mesh_spec):
        seen_meshes.append(dict(mesh_spec))
        state = {"x": jnp.zeros(())}

        def step_fn(s):
            return {"x": s["x"] + 1.0}, {"loss": 0.0}
        return step_fn, state

    driver = TrainDriver(store, build_step, checkpoint_every=4,
                         failure_schedule={6: "rescale"})
    report = driver.run(total_steps=8, mesh_spec={"n_devices": 8})
    assert report.rescales == 1
    assert seen_meshes[-1]["n_devices"] == 4      # shrunk after rescale


def test_heartbeat_monitor():
    mon = HeartbeatMonitor(n_workers=4, timeout=5.0)
    for w in range(4):
        mon.beat(w, 0.0)
    mon.beat(0, 8.0)
    assert set(mon.dead_workers(9.0)) == {1, 2, 3}


# ---------------------------------------------------------------------------
# restore hardening: damaged checkpoints fail loudly, naming the bad object
# ---------------------------------------------------------------------------

def test_restore_missing_shard_names_the_shard(tmp_path):
    store = CheckpointStore(str(tmp_path))
    tree = make_tree()
    store.save(3, tree, n_shards=4)
    os.remove(os.path.join(str(tmp_path), "step_00000003", "w.shard2.npy"))
    with pytest.raises(CheckpointError, match=r"w\.shard2\.npy"):
        store.restore(tree)


def test_restore_truncated_shard_names_the_shard(tmp_path):
    store = CheckpointStore(str(tmp_path))
    tree = make_tree()
    store.save(3, tree, n_shards=4)
    bad = os.path.join(str(tmp_path), "step_00000003",
                       "emb__table.shard1.npy")
    with open(bad, "r+b") as f:
        f.truncate(12)                       # mid-header: unreadable
    with pytest.raises(CheckpointError,
                       match=r"emb__table\.shard1\.npy.*unreadable"):
        store.restore(tree)


def test_restore_missing_full_object_names_it(tmp_path):
    store = CheckpointStore(str(tmp_path))
    tree = make_tree()
    store.save(3, tree, n_shards=4)          # scalar "step" saves full
    os.remove(os.path.join(str(tmp_path), "step_00000003", "step.full.npy"))
    with pytest.raises(CheckpointError, match=r"step\.full\.npy"):
        store.restore(tree)


def test_restore_truncated_full_object_names_it(tmp_path):
    store = CheckpointStore(str(tmp_path))
    tree = make_tree()
    store.save(3, tree, n_shards=4)
    bad = os.path.join(str(tmp_path), "step_00000003", "step.full.npy")
    with open(bad, "r+b") as f:
        f.truncate(4)
    with pytest.raises(CheckpointError,
                       match=r"step\.full\.npy.*unreadable"):
        store.restore(tree)


def test_restore_missing_manifest_is_loud(tmp_path):
    store = CheckpointStore(str(tmp_path))
    store.save(3, make_tree(), n_shards=4)
    os.remove(os.path.join(str(tmp_path), "step_00000003", "meta.json"))
    with pytest.raises(CheckpointError, match="manifest"):
        store.restore(make_tree())


def test_elastic_reshard_checkpoint_across_meshes():
    out = run_with_devices(textwrap.dedent("""
        import jax, numpy as np, tempfile
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.checkpoint.store import CheckpointStore
        tree = {"w": np.arange(64 * 16, dtype=np.float32).reshape(64, 16)}
        with tempfile.TemporaryDirectory() as d:
            store = CheckpointStore(d)
            store.save(1, tree)
            mesh8 = Mesh(np.array(jax.devices()[:8]), ("data",))
            specs = {"w": P("data")}
            back = store.restore_resharded(tree, mesh8, specs)
            assert np.array_equal(np.asarray(back["w"]), tree["w"])
            mesh2 = Mesh(np.array(jax.devices()[:2]), ("data",))
            back2 = store.restore_resharded(tree, mesh2, specs)
            assert np.array_equal(np.asarray(back2["w"]), tree["w"])
        print("OK")
    """), 8)
    assert "OK" in out
