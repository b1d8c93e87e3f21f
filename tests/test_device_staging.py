"""Device staging: a scan striped onto a mesh and all-gathered into a
replica on every chip (``core/staging.py`` ``device_replicate``,
``hedm/pipeline.py`` ``stage_scan``), and stage 1 reduced from the replicas
(``reduce_resident``).

Meshes of 2 and 4 chips run in one subprocess each, with virtual CPU
devices, and the tests read what it found; the one-chip cases run in the
pytest process. The gather's piece size is set by assigning
``GATHER_PIECE_BYTES`` in the subprocess, so that arrays of a few hundred
bytes take one piece, several even ones, or several with a short last one.
"""
import json
import os
import re
import textwrap

import jax
import numpy as np
import pytest
from conftest import run_with_devices
from jax.profiler import ProfileData
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import staging
from repro.hedm import pipeline as hp
from repro.kernels.hedm_reduce_ref import reference

FRAMES, SIZE, THRESHOLD = 12, 64, 200.0
SHARD_ROWS, ROW = 6, (3, 5)          # a chip's share of the gathered array
# a piece's rows -> the rows of each all-gather the replica takes
PIECES = {"one": (6, [6]), "even": (2, [2, 2, 2]), "short": (4, [4, 2])}
DTYPES = ("uint16", "float32")
WINDOWS = (1, 3, 16)                 # 16: one ragged step of the whole share


def _replicas(mesh):
    """For each dtype and piece size: the rows of each all-gather in the
    lowered program, and per chip whether its copy of the replica holds
    the array's bytes."""
    chips = mesh.shape["data"]
    rng = np.random.default_rng(chips)
    found = {}
    for dtype in DTYPES:
        shape = (chips * SHARD_ROWS,) + ROW
        x = (rng.integers(0, 2**16, shape).astype(dtype) if dtype == "uint16"
             else rng.standard_normal(shape).astype(dtype))
        row_bytes = x.dtype.itemsize * int(np.prod(ROW))
        for name, (rows, _) in PIECES.items():
            staging.GATHER_PIECE_BYTES = rows * chips * row_bytes
            staging._replicator.cache_clear()
            xs = staging.device_shard(mesh, x, P("data"))
            text = staging._replicator(mesh, "data").lower(xs).as_text()
            pieces = [int(n) for n in re.findall(
                r'"stablehlo\.all_gather".*: \(tensor<1x(\d+)x', text)]
            rep = staging.device_replicate(mesh, xs, "data")
            found[f"{name}-{dtype}"] = {
                "pieces": pieces,
                "chips": sorted(
                    [s.device.id, s.data.dtype == x.dtype
                     and np.asarray(s.data).tobytes() == x.tobytes()]
                    for s in rep.addressable_shards)}
    return found


def _scan():
    """12 frames of two spots: at 64x64 most frames' occupied rows
    overflow the compact copy back (8 rows) and some do not."""
    frames, dark = hp.simulate_detector_frames(FRAMES, size=SIZE, n_spots=2,
                                               seed=11)
    return (np.clip(np.rint(frames), 0, 65535).astype(np.uint16),
            dark.astype(np.uint16))


def _rows(reduced):
    return [[r.frame_id, r.n_signal_pixels, r.n_spots, r.peaks.dtype.str,
             r.peaks.tobytes().hex()] for r in reduced]


def _resident(mesh):
    """Stage 1 from a staged scan against ``reduce_frames_online``, at
    each window; each chip's copies of the scan and dark frame; and what
    staging a scan the chips do not divide raises."""
    scan, dark = _scan()
    resident = hp.stage_scan(mesh, scan, dark)
    found = {"windows": {}}
    for window in WINDOWS:
        found["windows"][window] = {
            "resident": sorted(_rows(r for step in hp.reduce_resident(
                resident, window=window, threshold=THRESHOLD)
                for r in step)),
            "online": _rows(r for c in hp.reduce_frames_online(
                scan, dark, window=window, threshold=THRESHOLD) for r in c)}
    found["replicas"] = sorted(
        [f.device.id, np.asarray(f.data).tobytes() == scan.tobytes(),
         np.asarray(d.data).tobytes() == dark.tobytes()]
        for f, d in zip(resident.frames.addressable_shards,
                        resident.dark.addressable_shards))
    try:
        hp.stage_scan(mesh, scan[:FRAMES - 1], dark)
        found["uneven"] = None
    except ValueError as e:
        found["uneven"] = str(e)
    return json.loads(json.dumps(found))   # the subprocess's key types


SCRIPT = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {tests!r})
    import jax, numpy as np
    from jax.sharding import Mesh
    import test_device_staging as t
    mesh = Mesh(np.array(jax.devices()), ("data",))
    found = {{"replicate": t._replicas(mesh)}}
    if {resident}:
        found["resident"] = t._resident(mesh)
    print("RESULT " + json.dumps(found))
""")


@pytest.fixture(scope="module")
def on_chips():
    """What each mesh found, by chip count, each measured once."""
    found = {}

    def get(chips):
        if chips not in found:
            if chips == 1:
                mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
                found[1] = {"resident": _resident(mesh)}
            else:
                out = run_with_devices(SCRIPT.format(
                    tests=os.path.dirname(os.path.abspath(__file__)),
                    resident=chips == 2), chips)
                line, = [ln for ln in out.splitlines()
                         if ln.startswith("RESULT ")]
                found[chips] = json.loads(line[len("RESULT "):])
        return found[chips]
    return get


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("pieces", list(PIECES))
@pytest.mark.parametrize("chips", [2, 4])
def test_device_replicate_is_byte_exact_on_every_chip_in_pieces(
        on_chips, chips, pieces, dtype):
    got = on_chips(chips)["replicate"][f"{pieces}-{dtype}"]
    assert got["pieces"] == PIECES[pieces][1]
    assert got["chips"] == [[d, True] for d in range(chips)]


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("chips", [1, 2])
def test_reduce_resident_equals_reduce_frames_online_bit_for_bit(
        on_chips, chips, window):
    got = on_chips(chips)["resident"]["windows"][str(window)]
    assert got["resident"] == got["online"]
    assert [r[0] for r in got["resident"]] == list(range(FRAMES))
    assert sum(r[2] for r in got["resident"]) > 0


@pytest.mark.parametrize("chips", [1, 2])
def test_every_chip_holds_the_scan_and_dark_frame_byte_exact(on_chips,
                                                             chips):
    assert on_chips(chips)["resident"]["replicas"] == [
        [d, True, True] for d in range(chips)]


def test_a_scan_two_chips_do_not_divide_is_refused(on_chips):
    message = on_chips(2)["resident"]["uneven"]
    assert message is not None
    assert f"{FRAMES - 1} frames" in message and "2 chips" in message


def test_copy_back_from_one_chip_carries_its_bytes_and_frames(tmp_path):
    scan, dark = _scan()
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    resident = hp.stage_scan(mesh, scan, dark)
    jax.profiler.start_trace(str(tmp_path))
    try:
        steps = list(hp.reduce_resident(resident, window=5,
                                        threshold=THRESHOLD))
    finally:
        jax.profiler.stop_trace()
    trace, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    spans = [dict(e.stats) for p in ProfileData.from_file(str(trace)).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events if e.name == "hedm.from_devices"]
    masks, _ = reference(jax.numpy.asarray(scan), jax.numpy.asarray(dark),
                         threshold=THRESHOLD)
    occupied = [int(m.any(axis=1).sum()) for m in np.asarray(masks)]
    rows = SIZE // 8
    # a frame's int32 row indices, row count and signal count, and its
    # occupied rows bit-packed; a uint8 mask a pixel for each frame whose
    # rows overflow
    compact = rows * 4 + 4 + rows * SIZE // 8 + 4
    assert [len(s) for s in steps] == [5, 5, 2]
    assert len(spans) == len(steps)
    for stats, step in zip(spans, steps):
        whole = sum(occupied[r.frame_id] > rows for r in step)
        assert stats == {"bytes": len(step) * compact + whole * SIZE * SIZE,
                         "frames": len(step), "dense_frames": whole,
                         "chips": 1}
    assert sum(s["dense_frames"] for s in spans) > 0
    assert sum(s["dense_frames"] for s in spans) < FRAMES


def test_device_replicate_and_staged_restore():
    out = run_with_devices(textwrap.dedent("""
        import jax, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core.staging import device_replicate, staged_restore
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2),
                    ("data", "model"))
        x = np.arange(64 * 8, dtype=np.float32).reshape(64, 8)
        xs = jax.device_put(x, NamedSharding(mesh, P("data")))
        rep = device_replicate(mesh, xs, "data")
        assert np.array_equal(np.asarray(rep), x)
        shards = {i: x[i * 16:(i + 1) * 16] for i in range(4)}
        r2 = staged_restore(mesh, shards, "data")
        assert np.array_equal(np.asarray(r2), x)
        print("OK")
    """), 8)
    assert "OK" in out
