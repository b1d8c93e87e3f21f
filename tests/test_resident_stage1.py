"""Stage 1 over a scan resident in HBM, staged across four chips.

``stage_scan`` stripes the scan 1/P per chip and all-gathers it into a
replica on every chip; ``reduce_resident`` has each chip filter its own
share of frames from its replica, a window at a time, and labels on the
host. Everything runs in one subprocess with four virtual CPU devices (the
main pytest process keeps its single device); the tests read what it
found. 40 frames over 4 chips is 10 a chip, so a window of 4 leaves a
ragged last step of 2. At 64x64 a frame's occupied rows come back compact
only up to 8 of them: most frames of six spots overflow and come back
whole, and a second scan of one spot a frame takes the compact path.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
CHIPS, FRAMES, SIZE, WINDOW = 4, 40, 64, 4

SCRIPT = textwrap.dedent(f"""
    import json, tempfile, glob
    import numpy as np, jax
    from jax.profiler import ProfileData
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.staging import device_replicate
    from repro.hedm import pipeline as hp
    from repro.kernels.hedm_reduce_ref import reference

    frames, dark = hp.simulate_detector_frames({FRAMES}, size={SIZE},
                                               n_spots=6, seed=11)
    scan = np.clip(np.rint(frames), 0, 65535).astype(np.uint16)
    dark = dark.astype(np.uint16)
    mesh = Mesh(np.array(jax.devices()[:{CHIPS}]), ("data",))
    out = {{}}

    def rows(reduced):
        return [[r.frame_id, r.n_signal_pixels, r.n_spots,
                 r.peaks.dtype.str, r.peaks.tobytes().hex()]
                for r in reduced]

    log_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(log_dir)
    resident = hp.stage_scan(mesh, scan, dark)
    steps = [rows(step) for step in hp.reduce_resident(
        resident, window={WINDOW}, threshold=200)]
    jax.profiler.stop_trace()
    out["steps"] = steps
    out["online"] = rows(r for c in hp.reduce_frames_online(
        scan, dark, window={WINDOW}, threshold=200) for r in c)

    def dense(scan):      # every frame labeled from its whole mask
        masks, counts = reference(jax.numpy.asarray(scan),
                                  jax.numpy.asarray(dark), threshold=200.0)
        found = []
        for f, (m, c) in enumerate(zip(np.asarray(masks),
                                       np.asarray(counts))):
            runs = hp.label_runs(m > 0)
            found.append(hp.ReducedFrame(f, int(c), runs.n,
                                         hp.run_centroids(runs, scan[f])))
        return rows(found), [int(m.any(axis=1).sum())
                             for m in np.asarray(masks)]

    out["dense"], out["occupied"] = dense(scan)
    sparse = np.clip(np.rint(hp.simulate_detector_frames(
        {FRAMES}, size={SIZE}, n_spots=1, seed=11)[0]), 0, 65535
        ).astype(np.uint16)
    out["sparse_resident"] = rows(r for step in hp.reduce_resident(
        hp.stage_scan(mesh, sparse, dark), window={WINDOW}, threshold=200)
        for r in step)
    out["sparse_dense"], out["sparse_occupied"] = dense(sparse)
    out["replicas"] = sorted(
        [s.device.id, bool(np.array_equal(np.asarray(s.data), scan)),
         bool(np.array_equal(np.asarray(s2.data), dark))]
        for s, s2 in zip(resident.frames.addressable_shards,
                         resident.dark.addressable_shards))
    trace, = glob.glob(log_dir + "/plugins/profile/*/*.xplane.pb")
    out["spans"] = [[e.name, dict(e.stats)]
                    for p in ProfileData.from_file(trace).planes
                    if p.name.startswith("/host:")
                    for line in p.lines for e in line.events
                    if e.name in ("hedm.to_devices", "hedm.all_gather",
                                  "hedm.from_devices")]
    out["scan_bytes"], out["dark_bytes"] = scan.nbytes, dark.nbytes

    try:
        hp.stage_scan(mesh, scan[:{FRAMES} - 2], dark)
        out["uneven"] = None
    except ValueError as e:
        out["uneven"] = str(e)

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **_: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    x = jax.device_put(np.arange(64 * 8, dtype=np.float32).reshape(64, 8),
                       NamedSharding(mesh, P("data")))
    device_replicate(mesh, x, "data").block_until_ready()
    n_first = len(compiles)
    second = device_replicate(mesh, x, "data").block_until_ready()
    out["replicate_compiles"] = [n_first, len(compiles) - n_first]
    out["replicate_equal"] = bool(np.array_equal(np.asarray(second),
                                                 np.asarray(x)))
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def found():
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={CHIPS}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    line, = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


def test_resident_path_equals_the_online_path_bit_for_bit(found):
    got = sorted((r for step in found["steps"] for r in step),
                 key=lambda r: r[0])
    assert got == found["online"]
    assert sum(r[2] for r in got) > 0


def test_resident_path_equals_labeling_whole_masks_bit_for_bit(found):
    """Both scans, the one whose frames mostly come back whole and the one
    whose frames all come back compact, give the peaks of labeling every
    frame's whole mask."""
    got = sorted((r for step in found["steps"] for r in step),
                 key=lambda r: r[0])
    assert got == found["dense"]
    assert sorted(found["sparse_resident"]) == found["sparse_dense"]
    assert max(found["sparse_occupied"]) <= SIZE // 8
    assert sum(n > SIZE // 8 for n in found["occupied"]) > FRAMES // 2
    assert sum(r[2] for r in found["sparse_dense"]) >= FRAMES


def test_every_frame_comes_back_once_with_global_ids_and_a_ragged_step(
        found):
    share = FRAMES // CHIPS
    widths = [len(step) // CHIPS for step in found["steps"]]
    assert widths == [WINDOW, WINDOW, share - 2 * WINDOW]
    ids = [r[0] for step in found["steps"] for r in step]
    assert sorted(ids) == list(range(FRAMES))
    first, last = found["steps"][0], found["steps"][-1]
    assert [r[0] for r in first] == [i * share + j for i in range(CHIPS)
                                     for j in range(WINDOW)]
    assert [r[0] for r in last] == [i * share + 2 * WINDOW + j
                                    for i in range(CHIPS) for j in range(2)]


def test_every_chip_holds_a_replica_of_the_scan_and_dark_frame(found):
    assert found["replicas"] == [[d, True, True] for d in range(CHIPS)]


def test_a_scan_the_chips_do_not_divide_is_refused(found):
    assert found["uneven"] is not None
    assert f"{FRAMES - 2} frames" in found["uneven"]
    assert f"{CHIPS} chips" in found["uneven"]


def test_a_second_replicate_on_the_same_mesh_compiles_nothing(found):
    first, second = found["replicate_compiles"]
    assert first >= 1 and second == 0
    assert found["replicate_equal"]


def test_staging_and_copy_back_spans_carry_their_bytes(found):
    spans = {}
    for name, stats in found["spans"]:
        spans.setdefault(name, []).append(stats)
    scan_b, dark_b = found["scan_bytes"], found["dark_bytes"]
    assert spans["hedm.to_devices"] == [
        {"bytes": scan_b + CHIPS * dark_b, "chips": CHIPS}]
    assert spans["hedm.all_gather"] == [{"bytes": (CHIPS - 1) * scan_b}]
    back = spans["hedm.from_devices"]
    assert len(back) == len(found["steps"])
    rows = SIZE // 8
    # a frame's int32 row indices, row count and signal count, and its
    # occupied rows bit-packed; a uint8 mask a pixel for each frame whose
    # rows overflow
    compact = rows * 4 + 4 + rows * SIZE // 8 + 4
    for s, step in zip(back, found["steps"]):
        whole = sum(found["occupied"][r[0]] > rows for r in step)
        assert (s["frames"], s["dense_frames"]) == (len(step), whole)
        assert s["bytes"] == len(step) * compact + whole * SIZE * SIZE
    assert sum(s["dense_frames"] for s in back) > 0
    assert all(s["chips"] == CHIPS for s in back)
