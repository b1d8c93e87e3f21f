"""The program's ``hedm.*`` host spans and the three readers built on them
(``centroid_ms_per_frame``, ``h2d_gb_per_s``, ``d2h_gb_per_s``): on
synthetic planes, on a trace recorded on the CPU around ``reduce_frames``,
and on the recorded v5e trace, whose program had no such spans."""
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

import harness
import host_spans
import trace_reduce as tr

MS = 1_000_000                     # ns
CELL = "nf_hedm_736.scan_w16"
RECORDED = Path(__file__).parent / "data" / "v5e_reduce_and_fit.xplane.pb"
READERS = ("centroid_ms_per_frame", "h2d_gb_per_s", "d2h_gb_per_s")


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start * MS, duration_ns=dur * MS,
              stats=stats)


def host(*events):
    return NS(name="/host:CPU", lines=[NS(name="python3",
                                          events=list(events))])


def synthetic():
    """Window [100, 1100] ms, two ``bench.call``s, each holding one
    reduction of 2 frames: copy in 20 ms of 2e8 bytes, filter, copy back
    50 ms of 4e7 bytes, index grid 10 ms, then label and centroids 30 ms a
    frame. One more reduction starts before the window and is left out;
    a device plane's ``hedm.`` op is not a host span."""
    events = [ev("bench.window", 100, 1000), ev("hedm.to_device", 50, 20,
                                                bytes=999)]
    for t in (200, 600):
        events += [ev("bench.call", t, 300),
                   ev("hedm.to_device", t, 20, bytes=200_000_000),
                   ev("hedm.filter", t + 20, 5),
                   ev("hedm.from_device", t + 25, 50, bytes=40_000_000),
                   ev("hedm.index_grid", t + 75, 10)]
        for f in range(2):
            events += [ev("hedm.label", t + 85 + 60 * f, 30),
                       ev("hedm.centroids", t + 115 + 60 * f, 30)]
    device = NS(name="/device:TPU:0", lines=[NS(
        name="XLA Ops", events=[ev("hedm.centroids", 300, 500)])])
    return [device, host(*events)]


def record(planes, work=4):
    """A ``RunRecord`` as the harness gives a reader, over ``planes``."""
    window = next((int(e.start_ns), int(e.start_ns + e.duration_ns))
                  for p in planes if p.name.startswith("/host:")
                  for line in p.lines for e in line.events
                  if e.name == harness.WINDOW_SPAN)
    return NS(cell=NS(name=CELL, config={}),
              window=harness.Window(work=work, failed=0, elapsed=1.0,
                                    calls=2),
              profile=NS(window=window), timers={}, compiles=0, peaks={})


def read(name, run):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py"
                               ).read(run)


def test_spans_inside_the_window_by_name():
    spans = host_spans.from_planes(synthetic(), (100 * MS, 1100 * MS))
    assert sorted(spans) == ["hedm.centroids", "hedm.filter",
                             "hedm.from_device", "hedm.index_grid",
                             "hedm.label", "hedm.to_device"]
    assert [len(spans[n]) for n in ("hedm.to_device", "hedm.label")] == [2, 4]
    assert host_spans.seconds(spans, "hedm.centroids") == pytest.approx(0.12)
    assert host_spans.seconds(spans, "hedm.centroids", "hedm.index_grid") \
        == pytest.approx(0.14)
    assert host_spans.gb_per_s(spans, "hedm.to_device") == pytest.approx(10)
    assert host_spans.gb_per_s(spans, "hedm.from_device") \
        == pytest.approx(0.8)
    assert host_spans.gb_per_s(spans, "hedm.filter") is None  # no bytes


def test_readers_on_a_synthetic_run(monkeypatch):
    planes = synthetic()
    monkeypatch.setattr(host_spans, "planes", lambda cell: planes)
    run = record(planes)
    # (2 grids x 10 ms + 4 frames x 30 ms) over 4 frames
    assert read("centroid_ms_per_frame", run) == pytest.approx(35.0)
    assert read("h2d_gb_per_s", run) == pytest.approx(10.0)
    assert read("d2h_gb_per_s", run) == pytest.approx(0.8)


@pytest.mark.parametrize("drop", ["hedm.", "bytes"])
def test_readers_report_nothing_without_their_spans(monkeypatch, drop):
    """A program without ``hedm.*`` spans, or with copy spans that carry no
    byte count, gives no reading."""
    planes = synthetic()
    events = planes[1].lines[0].events
    if drop == "hedm.":
        events[:] = [e for e in events if not e.name.startswith("hedm.")]
    else:
        for e in events:
            e.stats = {}
    monkeypatch.setattr(host_spans, "planes", lambda cell: planes)
    run = record(planes)
    values = {name: read(name, run) for name in READERS}
    if drop == "hedm.":
        assert values == dict.fromkeys(READERS)
    else:
        assert values["h2d_gb_per_s"] is values["d2h_gb_per_s"] is None
        assert values["centroid_ms_per_frame"] == pytest.approx(35.0)


def test_readers_report_nothing_without_a_trace(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    run = record(synthetic())
    assert {name: read(name, run) for name in READERS} \
        == dict.fromkeys(READERS)


def test_recorded_v5e_trace_has_no_program_spans(monkeypatch):
    """The recorded trace predates the program's spans: the readers give
    nothing, as on a program without them, and it reads as before."""
    from jax.profiler import ProfileData
    planes = ProfileData.from_file(str(RECORDED)).planes
    monkeypatch.setattr(host_spans, "planes", lambda cell: planes)
    p = tr.load(RECORDED)
    run = record(planes)
    assert run.profile.window == p.window
    assert {name: read(name, run) for name in READERS} \
        == dict.fromkeys(READERS)
    assert tr.module_time(p, "jit_hedm_reduce")[1] == 2


def test_readers_on_a_cpu_trace_of_reduce_frames(monkeypatch, tmp_path):
    """``reduce_frames`` traced inside the window where the harness keeps
    the cell's trace: every reader finds its spans, and the copies' bytes
    are the arrays' sizes."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.hedm.pipeline import reduce_frames, simulate_detector_frames
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    frames, dark = simulate_detector_frames(3, size=64, n_spots=4, seed=5)
    frames, dark = frames.astype(np.uint16), dark.astype(np.uint16)
    reduce_frames(frames, dark, use_kernel=False)            # compiled
    jax.profiler.start_trace(str(tmp_path / CELL))
    try:
        with TraceAnnotation(harness.WINDOW_SPAN):
            for _ in range(2):
                with TraceAnnotation("bench.reduce_frames"):
                    reduce_frames(frames, dark, use_kernel=False)
    finally:
        jax.profiler.stop_trace()
    planes = host_spans.planes(CELL)
    run = record(planes, work=6)
    spans = host_spans.of(run)
    assert [len(spans[n]) for n in ("hedm.to_device", "hedm.index_grid",
                                    "hedm.centroids")] == [2, 2, 6]
    assert [st["bytes"] for _, st in spans["hedm.to_device"]] \
        == [frames.nbytes + dark.nbytes] * 2
    assert [st["bytes"] for _, st in spans["hedm.from_device"]] \
        == [3 * 64 * 64 + 3 * 4] * 2
    values = {name: read(name, run) for name in READERS}
    assert all(v > 0 for v in values.values()), values
    assert values["centroid_ms_per_frame"] == pytest.approx(
        1e3 * host_spans.seconds(spans, "hedm.centroids",
                                 "hedm.index_grid") / 6)
