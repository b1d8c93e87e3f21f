"""Host-clock spans of the stage-1 chip path.

``reduce_frames`` marks its copies, filter, labeling, index grid and
centroids with ``hedm.*`` spans on the JAX profiler's host plane, and its
copies' spans carry the bytes they moved as the stat ``bytes``; each frame's
labeling and centroid spans carry the runs and signal pixels they handled
(stats ``runs`` and ``pixels``). The spans cost nothing while no trace
records, and outputs are the same with a trace recording or not.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData, TraceAnnotation

from repro.hedm.pipeline import (reduce_frames, reduce_frames_online,
                                 simulate_detector_frames, _union_find_label)
from repro.kernels.hedm_reduce_ref import reference

SPANS = ("hedm.to_device", "hedm.filter", "hedm.from_device",
         "hedm.index_grid", "hedm.label", "hedm.centroids")
F, SIZE = 3, 64
PIXELS = SIZE * SIZE


@pytest.fixture(scope="module")
def scan():
    """Three 64x64 frames as the detector writes them (uint16)."""
    frames, dark = simulate_detector_frames(F, size=SIZE, n_spots=4, seed=5)
    return frames.astype(np.uint16), dark.astype(np.uint16)


def _as_tuples(reduced):
    return [(r.frame_id, r.n_signal_pixels, r.n_spots, r.peaks.dtype,
             r.peaks.tobytes()) for r in reduced]


def _online(frames, dark, use_kernel):
    return [r for chunk in reduce_frames_online(frames, dark, window=2,
                                                use_kernel=use_kernel)
            for r in chunk]


def _traced(log_dir, fn):
    """Run ``fn`` inside a ``caller`` span under a profiler trace; returns
    what ``fn`` returned and the host events named ``hedm.*`` or ``caller``
    as ``(name, start_ns, end_ns, stats)``, in order of start."""
    jax.profiler.start_trace(str(log_dir))
    try:
        with TraceAnnotation("caller"):
            out = fn()
    finally:
        jax.profiler.stop_trace()
    trace, = log_dir.glob("plugins/profile/*/*.xplane.pb")
    events = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
               dict(e.stats))
              for plane in ProfileData.from_file(str(trace)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("hedm.") or e.name == "caller"]
    return out, sorted(events, key=lambda e: e[1])


@pytest.mark.parametrize("use_kernel", [False, True])
def test_outputs_are_identical_with_a_trace_recording_or_not(scan, tmp_path,
                                                             use_kernel):
    frames, dark = scan
    plain = reduce_frames(frames, dark, use_kernel=use_kernel)
    plain_online = _online(frames, dark, use_kernel)
    (traced, traced_online), _ = _traced(
        tmp_path, lambda: (reduce_frames(frames, dark, use_kernel=use_kernel),
                           _online(frames, dark, use_kernel)))
    assert sum(r.n_spots for r in plain) > 0
    assert _as_tuples(traced) == _as_tuples(plain)
    assert _as_tuples(traced_online) == _as_tuples(plain_online)
    assert _as_tuples(plain_online) == _as_tuples(plain)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_trace_holds_every_span_inside_the_caller(scan, tmp_path,
                                                  use_kernel):
    frames, dark = scan
    _, events = _traced(
        tmp_path, lambda: reduce_frames(frames, dark, use_kernel=use_kernel))
    names = [n for n, _, _, _ in events]
    assert names[0] == "caller" and names.count("caller") == 1
    _, lo, hi, _ = events[0]
    assert all(lo <= s and e <= hi for _, s, e, _ in events)
    assert set(names[1:]) == set(SPANS)
    assert names[1:5] == ["hedm.to_device", "hedm.filter",
                          "hedm.from_device", "hedm.index_grid"]
    assert names[5:] == ["hedm.label", "hedm.centroids"] * F


@pytest.mark.parametrize("use_kernel,dtype,stored", [
    (False, np.uint16, np.uint16), (True, np.uint16, np.uint16),
    # without x64 a float64 stack lands on the device as float32
    (False, np.float64, np.float32)])
def test_copy_spans_carry_the_bytes_moved(scan, tmp_path, use_kernel, dtype,
                                          stored):
    frames, dark = (a.astype(dtype) for a in scan)
    _, events = _traced(
        tmp_path, lambda: reduce_frames(frames, dark, use_kernel=use_kernel))
    moved = {n: st["bytes"] for n, _, _, st in events if "bytes" in st}
    # frames and dark frame in; a uint8 mask a pixel and an int32 count a
    # frame back
    assert moved == {
        "hedm.to_device": (F + 1) * PIXELS * np.dtype(stored).itemsize,
        "hedm.from_device": F * PIXELS + F * 4}


def test_online_reduction_has_one_set_of_spans_a_window(scan, tmp_path):
    frames, dark = scan
    _, events = _traced(tmp_path, lambda: _online(frames, dark, False))
    names = [n for n, _, _, _ in events]
    for once_a_call in SPANS[:4]:
        assert names.count(once_a_call) == 2            # windows of 2 and 1
    assert names.count("hedm.label") == names.count("hedm.centroids") == F
    assert sum(st["bytes"] for n, _, _, st in events
               if n == "hedm.to_device") == (F + 2) * PIXELS * 2


@pytest.mark.parametrize("use_kernel", [False, True])
def test_label_and_centroid_spans_count_runs_and_pixels(scan, tmp_path,
                                                        use_kernel):
    frames, dark = scan
    _, events = _traced(
        tmp_path, lambda: reduce_frames(frames, dark, use_kernel=use_kernel))
    masks, _ = reference(jnp.asarray(frames), jnp.asarray(dark),
                         threshold=200.0)
    dense = [_union_find_label(m > 0)[0] > 0 for m in np.asarray(masks)]
    # a run starts at a labelled pixel in column 0 or after an unlabelled one
    runs = [int(np.count_nonzero(d & ~np.pad(d, ((0, 0), (1, 0)))[:, :-1]))
            for d in dense]
    pixels = [int(np.count_nonzero(d)) for d in dense]
    stats = {name: [st for n, _, _, st in events if n == name]
             for name in SPANS}
    assert sum(pixels) > 0
    assert [st.get("runs") for st in stats["hedm.label"]] == runs
    assert [st.get("pixels") for st in stats["hedm.centroids"]] == pixels
    assert len(stats["hedm.index_grid"]) == 1
