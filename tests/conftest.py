import os
import subprocess
import sys

import numpy as np
import pytest

# Tests run on the default single CPU device; a test that needs a mesh
# starts a subprocess with virtual devices (`run_with_devices`).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def run_with_devices(code: str, n: int) -> str:
    """Run ``code`` in a Python subprocess that sees ``n`` virtual CPU
    devices (the pytest process keeps its one device); returns its
    standard output and fails the test if it exits non-zero."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


# ---------------------------------------------------------------------------
# shared scenario builders (hoisted from test_staging / test_datasvc /
# test_faults / test_topology, which each carried a copy-pasted variant).
# Import them with `from conftest import ...`; the fixtures below wrap the
# common default shapes for tests that just need "a fabric" or "a service".
# ---------------------------------------------------------------------------

def make_fabric(n_hosts=8, n_files=4, size=1 << 16, seed=0, topology=None,
                prefix="d", **kw):
    """A BGQ-calibrated fabric with `n_files` random files of `size` bytes
    installed at ``{prefix}/f{i}.bin``. Returns ``(fabric, paths)``.
    Extra keywords (``faults=``, ``ranks_per_host=``...) pass through to
    :class:`repro.core.fabric.Fabric`."""
    from repro.core.fabric import BGQ, Fabric
    fab = Fabric(n_hosts=n_hosts, constants=BGQ, topology=topology, **kw)
    rng = np.random.default_rng(seed)
    paths = []
    for i in range(n_files):
        p = f"{prefix}/f{i}.bin"
        fab.fs.put(p, rng.integers(0, 255, size, dtype=np.uint8))
        paths.append(p)
    return fab, paths


def make_service(n_hosts=8, sizes=(4, 4, 4), file_bytes=1 << 12,
                 budget_files=8, seed=0, **service_kw):
    """A fabric with datasets d0..dN of `sizes[i]` files each, registered
    on a service whose budget holds `budget_files` files. Returns
    ``(fabric, service)``; extra keywords pass through to
    :class:`repro.core.datasvc.StagingService`."""
    from repro.core.datasvc import StagingService
    from repro.core.fabric import BGQ, Fabric
    fab = Fabric(n_hosts=n_hosts, constants=BGQ)
    rng = np.random.default_rng(seed)
    svc = StagingService(fab, budget_bytes=budget_files * file_bytes,
                         **service_kw)
    for d, n_files in enumerate(sizes):
        paths = []
        for i in range(n_files):
            p = f"d{d}/f{i}.bin"
            fab.fs.put(p, rng.integers(0, 255, file_bytes, dtype=np.uint8))
            paths.append(p)
        svc.register(f"d{d}", paths=paths)
    return fab, svc


@pytest.fixture
def small_fabric():
    """Default 8-host fabric with 4 x 64 KiB files: ``(fabric, paths)``."""
    return make_fabric()


@pytest.fixture
def service8():
    """Default 8-host service with three 4-file datasets under an
    8-file budget: ``(fabric, service)``."""
    return make_service()
