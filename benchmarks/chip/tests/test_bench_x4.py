"""The four-chip cell ``nf_hedm_736_x4.stage1`` at a tiny size on the CPU,
through its own cell and configuration files, in a subprocess with four
virtual devices: it reads ``correct`` true, and false under each fault the
cell can have (a replica changed on one chip, one chip's share of a step
left out, a count off by one) and under the bfloat16 control. Its readers, and the bytes the all-gather
has to move, on synthetic traces; the replica checksums on planted
changes; and a program without the resident path refused at once."""
import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

import harness
import host_spans
import ici_costs
import trace_reduce as tr

ROOT = Path(__file__).resolve().parents[3]
CELL = "nf_hedm_736_x4.stage1"
SEED = 2**31 + 4321
MS = 1_000_000                     # ns

SCRIPT = textwrap.dedent(f"""
    import json, time
    import numpy as np, jax
    import controls, harness
    from repro.hedm import pipeline

    def tiny():
        cell = harness.load_cell({CELL!r})
        cell.config.update(frames=40, frame_size=64)
        cell.spec["compare"].update(sample_frames=8)
        cell.spec["traffic"].update(window=4)
        return cell

    stage, reduce = pipeline.stage_scan, pipeline.reduce_resident

    def replica_frame_altered(mesh, scan, dark, axis="data"):
        # frame 0 is chip 0's to reduce: only chip 1's copy of it changes
        res = stage(mesh, scan, dark, axis)
        shards = res.frames.addressable_shards
        arrays = [s.data for s in shards]
        one = np.array(arrays[1])
        one[0, 5, 7] += 1
        arrays[1] = jax.device_put(one, shards[1].device)
        frames = jax.make_array_from_single_device_arrays(
            res.frames.shape, res.frames.sharding, arrays)
        return res._replace(frames=frames)

    def chip_share_left_out(resident, window=16, threshold=200.0):
        share = len(resident.host) // 4
        for k, step in enumerate(reduce(resident, window, threshold)):
            yield [r for r in step
                   if k or not share <= r.frame_id < 2 * share]

    def count_off_by_one(resident, window=16, threshold=200.0):
        for step in reduce(resident, window, threshold):
            step[0].n_signal_pixels += 1
            yield step

    def bf16_control(resident, window=16, threshold=200.0):
        # the reference in bfloat16, over the frames each step is due
        share, dark = len(resident.host) // 4, np.asarray(resident.dark)
        for first in range(0, share, window):
            ids = [i * share + f for i in range(4)
                   for f in range(first, min(first + window, share))]
            step = next(controls.scan_control()(
                resident.host[ids], dark, len(ids), threshold))
            for r, f in zip(step, ids):
                r.frame_id = f
            yield step

    patches = {{"replica_frame_altered": ("stage_scan", replica_frame_altered),
               "chip_share_left_out": ("reduce_resident", chip_share_left_out),
               "count_off_by_one": ("reduce_resident", count_off_by_one),
               "bf16_control": ("reduce_resident", bf16_control)}}
    out = {{}}
    for case in ["clean", *patches]:
        if case in patches:
            name, fn = patches[case]
            setattr(pipeline, name, fn)
        out[case] = harness.run_cell({CELL!r}, {SEED}, 0.5, False,
                                     time.perf_counter(), cell=tiny(),
                                     require_chip=False)
        pipeline.stage_scan, pipeline.reduce_resident = stage, reduce
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The clean cell and each fault, in one process with four virtual CPU
    devices; its compile cache and traces stay out of the checkout."""
    tmp = tmp_path_factory.mktemp("x4")
    prelude = (f"import harness; harness.CACHE_DIR = harness.Path("
               f"{str(tmp / 'cache')!r}); harness.TRACE_DIR = harness.Path("
               f"{str(tmp / 'traces')!r})\n")
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_COMPILATION_CACHE_DIR")}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(harness.HERE),
                                           str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", prelude + SCRIPT], env=env,
                       cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    line, = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


def test_tiny_cell_runs_correct_on_four_devices(runs):
    result = runs["clean"]
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["count"] == 4
    assert set(result["metrics"]) == {"stage1_frames_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["checks"]["replica_mismatch_frames"] == {"value": 0,
                                                          "limit": 0}
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("fault,check", [
    ("replica_frame_altered", "replica_mismatch_frames"),
    ("chip_share_left_out", "frames_missing"),
    ("count_off_by_one", "count_mismatch_frames")])
def test_fault_is_not_correct(runs, fault, check):
    result = runs[fault]
    assert result["correct"] is False, result["checks"]
    assert result["checks"][check]["value"] > 0
    if fault == "replica_frame_altered":      # only one chip's copy of one
        assert result["checks"][check]["value"] == 1       # frame changed


def test_control_in_programs_place_is_not_correct(runs):
    """The reference in bfloat16 in place of the resident path: the limits
    the cell shares with ``scan_w16`` refuse it here too."""
    result = runs["bf16_control"]
    assert result["correct"] is False, result["checks"]
    assert result["checks"]["replica_mismatch_frames"]["value"] == 0


def test_a_program_without_the_resident_path_is_refused_at_once(
        monkeypatch, tmp_path):
    from repro.hedm import pipeline
    monkeypatch.delattr(pipeline, "stage_scan")
    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path / "cache")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    t0 = time.perf_counter()
    with pytest.raises(harness.BenchError, match="stage_scan"):
        harness.run_cell(CELL, SEED, 1.0, False, t0, require_chip=False)
    assert time.perf_counter() - t0 < 30


def _driver():
    return harness.load_module(harness.HERE / "drivers" / "scan_striped.py")


def test_replica_checksums_see_a_changed_a_moved_pixel_and_swapped_frames():
    checksums = _driver().checksums
    rng = np.random.default_rng(5)
    scan = rng.integers(0, 65536, (3, 16, 16), dtype=np.uint16)

    def sums(s):
        return checksums(s.sum(axis=2, dtype=np.int64),
                         s.sum(axis=1, dtype=np.int64))

    want = sums(scan)
    flat = scan.reshape(3, -1).astype(np.int64)
    assert want[:, 0].tolist() == flat.sum(axis=1).tolist()
    assert want[:, 1].tolist() == (flat * np.arange(256)).sum(axis=1).tolist()
    changed = scan.copy()
    changed[1, 3, 4] ^= 1
    swapped = scan[[1, 0, 2]]
    for bad, frames in ((changed, [1]), (swapped, [0, 1])):
        assert np.flatnonzero(np.any(sums(bad) != want, axis=1)).tolist() \
            == frames
    # two pixels of one frame trade places: the plain sum cannot see it
    traded = scan.copy()
    traded[2, 0, 0], traded[2, 9, 1] = scan[2, 9, 1], scan[2, 0, 0]
    assert scan[2, 0, 0] != scan[2, 9, 1]
    got = sums(traded)
    assert got[2, 0] == want[2, 0] and got[2, 1] != want[2, 1]


def test_least_all_gather_bytes_of_the_scan():
    replica = 736 * 2048 * 2048 * 2
    assert replica == 6_174_015_488
    assert ici_costs.all_gather_min_bytes_per_chip(replica, 4) \
        == 4_630_511_616
    assert ici_costs.all_gather_min_bytes_per_chip(replica, 1) == 0


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start * MS, duration_ns=dur * MS,
              stats=stats)


def line(name, *events):
    return NS(name=name, events=list(events))


def synthetic():
    """Window [100, 1100] ms on four chips. On each chip the gather program
    ran once, 200-240 ms (two ops, 200-230 and 225-240), and a filter step
    600-610 ms. On the host, two stagings, one before the window: each
    copies 3e9 bytes in 500 ms (to_devices) and gathers; and two copies
    back of 2.5e8 bytes in 100 ms each."""
    devices = [NS(name=f"/device:TPU:{d}", lines=[
        line("XLA Modules", ev("jit_staging_all_gather(3)", 200, 40),
             ev("jit_filter_own_frames(4)", 600, 10)),
        line("XLA Ops", ev("all-gather.1", 200, 30),
             ev("fusion.2", 225, 15), ev("hedm", 600, 10))])
        for d in range(4)]
    host = NS(name="/host:CPU", lines=[line(
        "python3", ev("bench.window", 100, 1000),
        ev("hedm.to_devices", 0, 50, bytes=999, chips=4),
        ev("hedm.to_devices", 110, 500, bytes=3_000_000_000, chips=4),
        ev("hedm.all_gather", 610, 40, bytes=9_000_000_000),
        ev("hedm.from_devices", 700, 100, bytes=250_000_000, chips=4),
        ev("hedm.from_devices", 900, 100, bytes=250_000_000, chips=4))])
    return [*devices, host]


def record(planes, monkeypatch, calls=4):
    monkeypatch.setattr(host_spans, "planes", lambda cell: planes)
    cell = harness.load_cell(CELL)
    return harness.RunRecord(
        cell=cell, window=harness.Window(work=128, failed=0, elapsed=1.0,
                                         calls=calls),
        profile=tr.profile_from_planes(planes), timers={}, compiles=0,
        peaks={"ici_bits_per_s": 1600e9})


def read(name, run):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py"
                               ).read(run)


def test_readers_on_a_synthetic_four_chip_run(monkeypatch):
    run = record(synthetic(), monkeypatch)
    assert read("stripe_h2d_gb_per_s", run) == pytest.approx(6.0)
    assert read("d2h_gb_per_s.x4", run) == pytest.approx(2.5)
    assert read("compiles_per_call.x4", run) == 0.0
    # each chip busy 200-240 and 600-610 ms of the 1-s window
    assert read("device_idle_share.x4", run) == pytest.approx(95.0)
    # 4 runs x 4,630,511,616 B at 200 GB/s, over 4 x 40 ms
    assert read("allgather_ici_roofline", run) == pytest.approx(
        100 * 4_630_511_616 / 200e9 / 0.040)


def test_readers_report_nothing_without_their_spans_or_program(
        monkeypatch):
    """A program without the staging spans or the gather program (the
    parent of this cell) gives no reading from those readers."""
    planes = synthetic()
    host = planes[-1].lines[0]
    host.events = [e for e in host.events if not e.name.startswith("hedm.")]
    for p in planes[:-1]:
        for ln in p.lines:
            ln.events = [e for e in ln.events
                         if not e.name.startswith("jit_staging")
                         and e.name not in ("all-gather.1", "fusion.2")]
    run = record(planes, monkeypatch, calls=0)
    for name in ("stripe_h2d_gb_per_s", "d2h_gb_per_s.x4",
                 "allgather_ici_roofline", "compiles_per_call.x4"):
        assert read(name, run) is None, name
