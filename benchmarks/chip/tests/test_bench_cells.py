"""The cell end to end at a tiny size on the CPU (kernels in interpret
mode), through its own cell and configuration files, with the harness's look
for a chip skipped; and the comparison that decides ``correct`` seen to
fail under each fault the cell can have and under its control."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import controls
import harness

ROOT = Path(__file__).resolve().parents[3]
SCAN = "nf_hedm_736.scan_w16"
SEED = 2**31 + 1234


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch, tmp_path):
    """Keep the CPU's compiled programs out of the checkout."""
    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path / "cache")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "traces")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)


def tiny(name):
    """The cell as its files give it, at a size the CPU runs in seconds."""
    cell = harness.load_cell(name)
    cell.config.update(frames=40, frame_size=64)
    cell.spec["compare"].update(sample_frames=8)
    return cell


def run(name, seconds=1.0, cell=None):
    return harness.run_cell(name, SEED, seconds, False, time.perf_counter(),
                            cell=cell or tiny(name), require_chip=False)


@pytest.mark.parametrize("name", [SCAN])
def test_cell_runs_correct_and_reports_its_metrics(name):
    result = run(name)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    want = {m["name"] for m in spec["end_to_end"]
            if name in m.get("workloads", [name])}
    assert set(result["metrics"]) == want
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(result)[-1] == "checks"


def test_scan_inputs_follow_the_seed():
    import scangen
    cfg = tiny(SCAN).config
    a, da = scangen.render_scan(cfg, SEED, chunk=16)
    b, db = scangen.render_scan(cfg, SEED, chunk=7)
    c, _ = scangen.render_scan(cfg, SEED + 2**32, chunk=16)
    assert np.array_equal(a, b) and np.array_equal(da, db)
    assert not np.array_equal(a, c)
    assert len({f.tobytes() for f in a}) == len(a)       # every frame differs
    assert 7.5 < da.mean() < 8.5


def _patched(module, name, wrap):
    return controls.in_place_of(module, name, wrap(getattr(module, name)))


def _count_off_by_one(hedm_reduce):
    def altered(frames, dark, threshold=100.0):
        masks, counts = hedm_reduce(frames, dark, threshold=threshold)
        return masks, counts.at[0].add(1)
    return altered


def _half_window(hedm_reduce):
    def half(frames, dark, threshold=100.0):
        masks, counts = hedm_reduce(frames[: len(frames) // 2], dark,
                                    threshold=threshold)
        pad = len(frames) - len(masks)
        return (np.concatenate([masks, np.zeros((pad,) + masks.shape[1:],
                                                masks.dtype)]),
                np.concatenate([counts, np.zeros(pad, counts.dtype)]))
    return half


def _peaks_shifted(reduce_frames):
    def shifted(*args, **kwargs):
        out = reduce_frames(*args, **kwargs)
        for r in out:
            r.peaks = r.peaks + np.float32(0.01)
        return out
    return shifted



def _faults():
    from repro.hedm import pipeline
    from repro.kernels import ops
    return {
        "scan_answer_altered": (SCAN, lambda: _patched(
            ops, "hedm_reduce", _count_off_by_one)),
        "scan_peaks_altered": (SCAN, lambda: _patched(
            pipeline, "reduce_frames", _peaks_shifted)),
        "scan_half_window_left_out": (SCAN, lambda: _patched(
            ops, "hedm_reduce", _half_window)),
    }


@pytest.mark.parametrize("fault", ["scan_answer_altered", "scan_peaks_altered",
                                   "scan_half_window_left_out"])
def test_fault_in_timed_path_is_not_correct(fault):
    name, patch = _faults()[fault]
    with patch():
        result = run(name)
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("name", [SCAN])
def test_control_in_programs_place_is_not_correct(name):
    from repro.hedm import pipeline
    with controls.in_place_of(pipeline, "reduce_frames_online",
                              controls.scan_control()):
        result = run(name)
    assert result["correct"] is False, result["checks"]


def _run_py(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_COMPILATION_CACHE_DIR")}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", SCAN,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_prints_no_result():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_run_with_only_the_benchmark_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmarks" / "chip", tmp_path / "benchmarks"
                    / "chip", ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_every_cell_and_metric_has_its_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (harness.HERE / "drivers" / f"{cell.spec['driver']}.py"
                ).is_file()
        assert set(cell.limits) and all(
            isinstance(v, (int, float)) for v in cell.limits.values())
    for m in spec["per_layer"]:
        assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file()

