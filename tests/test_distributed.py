"""Device-level distribution tests (run in a subprocess with 8 fake devices
so the main pytest process keeps the default single device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_device_replicate_and_staged_restore():
    out = run_with_devices(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.staging import device_replicate, staged_restore
        from repro.core.compat import make_auto_mesh
        mesh = make_auto_mesh((4, 2), ("data", "model"))
        x = np.arange(64 * 8, dtype=np.float32).reshape(64, 8)
        xs = jax.device_put(x, NamedSharding(mesh, P("data")))
        rep = device_replicate(mesh, xs, "data")
        assert np.array_equal(np.asarray(rep), x)
        shards = {i: x[i * 16:(i + 1) * 16] for i in range(4)}
        r2 = staged_restore(mesh, shards, "data")
        assert np.array_equal(np.asarray(r2), x)
        print("OK")
    """))
    assert "OK" in out


@pytest.mark.slow
def test_sharded_train_step_runs_and_matches_single_device():
    out = run_with_devices(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import ShapeConfig
        from repro.configs.registry import get_smoke_config
        from repro.distributed.sharding import (make_ctx, param_pspecs,
                                                input_pspecs)
        from repro.launch.mesh import make_mesh
        from repro.train.optimizer import OptConfig
        from repro.train.train_step import init_train_state, make_train_step

        cfg = get_smoke_config("qwen3_32b")
        opt = OptConfig(total_steps=10, warmup_steps=2)
        shape = ShapeConfig("s", "train", 32, 4, 1, True)
        batch = {"tokens": jnp.ones((4, 32), jnp.int32),
                 "labels": jnp.ones((4, 32), jnp.int32)}
        # single device reference
        params, opt_state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
        step = jax.jit(make_train_step(cfg, shape, opt))
        _, _, m_ref = step(params, opt_state, batch)
        # sharded over (2,4) mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = make_ctx(mesh)
        params, opt_state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
        pspecs = param_pspecs(cfg, params, ctx)
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda x: isinstance(x, P))
        params = jax.tree.map(jax.device_put, params, sh)
        step2 = jax.jit(make_train_step(cfg, shape, opt, ctx=ctx))
        _, _, m = step2(params, opt_state, batch)
        assert abs(float(m["loss"]) - float(m_ref["loss"])) < 1e-2, \\
            (float(m["loss"]), float(m_ref["loss"]))
        print("OK", float(m["loss"]))
    """))
    assert "OK" in out


def test_compressed_dcn_train_step_on_pod_mesh():
    out = run_with_devices(textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import ShapeConfig
        from repro.configs.registry import get_smoke_config
        from repro.distributed.sharding import make_ctx, param_pspecs
        from repro.launch.mesh import make_mesh
        from repro.train.optimizer import OptConfig
        from repro.train.train_step import init_train_state, make_train_step

        cfg = get_smoke_config("internlm2_20b")
        opt = OptConfig(total_steps=10, warmup_steps=2)
        shape = ShapeConfig("s", "train", 16, 4, 1, True)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        ctx = make_ctx(mesh)
        params, opt_state = init_train_state(jax.random.PRNGKey(0), cfg, opt,
                                             compress_dcn=True)
        step = jax.jit(make_train_step(cfg, shape, opt, ctx=ctx,
                                       compress_dcn=True))
        batch = {"tokens": jnp.ones((16, 16), jnp.int32),
                 "labels": jnp.ones((16, 16), jnp.int32)}
        p, o, m = step(params, opt_state, batch)
        assert jnp.isfinite(m["loss"])
        print("OK", float(m["loss"]))
    """))
    assert "OK" in out


def test_elastic_reshard_checkpoint_across_meshes():
    out = run_with_devices(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.store import CheckpointStore
        from repro.launch.mesh import make_mesh
        tree = {"w": np.arange(64 * 16, dtype=np.float32).reshape(64, 16)}
        with tempfile.TemporaryDirectory() as d:
            store = CheckpointStore(d)
            store.save(1, tree)
            mesh8 = make_mesh((8,), ("data",))
            specs = {"w": P("data")}
            back = store.restore_resharded(tree, mesh8, specs)
            assert np.array_equal(np.asarray(back["w"]), tree["w"])
            mesh2 = make_mesh((2,), ("data",))
            back2 = store.restore_resharded(tree, mesh2, specs)
            assert np.array_equal(np.asarray(back2["w"]), tree["w"])
        print("OK")
    """))
    assert "OK" in out
