"""chip_smoke.py's phases at a tiny size on the CPU (kernel in interpret mode).

Only the script's ``main()`` insists on a TPU; its phase functions take
their sizes, so the same checks run here on a few 64x64 frames.
"""
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)


@pytest.fixture(scope="module")
def scan():
    return smoke.make_scan(6, 64, 3, seed=0)


def test_make_scan_is_uint16_and_cycles_its_unique_frames(scan):
    frames, dark = scan
    assert frames.shape == (6, 64, 64) and frames.dtype == np.uint16
    assert dark.dtype == np.uint16
    assert np.array_equal(frames[0], frames[3])
    assert not np.array_equal(frames[0], frames[1])


def test_stage1_and_staged_phases_agree_on_cpu(scan):
    frames, dark = scan
    reduced = smoke.phase_stage1(frames, dark, window=2)
    assert [r.frame_id for r in reduced] == list(range(6))
    assert sum(r.n_spots for r in reduced) > 0
    smoke.phase_staged(frames[:4], dark, reduced[:4])
    other = smoke.phase_stage1(frames[:4] + 1, dark, window=2)
    with pytest.raises(smoke.SmokeFailure):
        smoke.phase_staged(frames[:4], dark, other)


def test_stage2_phase_checks_recovered_share():
    assert smoke.phase_stage2(64, min_share=0.7) >= 0.7
    with pytest.raises(smoke.SmokeFailure):
        smoke.phase_stage2(64, min_share=1.01)


def test_kernel_check_refuses_interpret_mode():
    """Off the chip the kernel runs in the interpreter: the check that the
    smoke relies on to prove a compiled kernel must say so."""
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_kernel_compiled(2, 64)


def test_main_refuses_to_run_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "platform=cpu" in out


def test_compile_cache_dir_honours_the_environment(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert smoke.use_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert smoke.use_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_replicate_phase_on_four_virtual_devices():
    code = textwrap.dedent(f"""
        import importlib.util, jax
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        window, _ = smoke.make_scan(8, 64, 8, seed=1)
        smoke.phase_replicate(window, jax.devices()[:4])
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "byte_exact_replicas=4/4" in out.stdout
    assert "staged=65536 naive=262144" in out.stdout
