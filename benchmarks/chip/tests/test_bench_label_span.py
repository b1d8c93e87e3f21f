"""The reader ``hedm_label_ms_per_frame``: the program's ``hedm.label``
spans over the window's frames, on synthetic planes, with nothing to read,
and on a trace recorded on the CPU around ``reduce_frames``."""
import numpy as np
import pytest

import harness
import host_spans
from test_bench_host_spans import CELL, ev, read, record, synthetic

READER = "hedm_label_ms_per_frame"


def test_reads_the_label_spans_of_a_synthetic_run(monkeypatch):
    planes = synthetic()
    monkeypatch.setattr(host_spans, "planes", lambda cell: planes)
    # 4 frames x 30 ms of labeling over 4 frames; the label span that lies
    # before the window is left out
    planes[1].lines[0].events.append(ev("hedm.label", 0, 90))
    assert read(READER, record(planes)) == pytest.approx(30.0)
    assert read(READER, record(planes, work=8)) == pytest.approx(15.0)


@pytest.mark.parametrize("missing", ["spans", "trace", "frames"])
def test_reports_nothing_without_spans_trace_or_frames(monkeypatch, tmp_path,
                                                       missing):
    planes = synthetic()
    if missing == "spans":
        events = planes[1].lines[0].events
        events[:] = [e for e in events if e.name != "hedm.label"]
    if missing == "trace":
        monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    else:
        monkeypatch.setattr(host_spans, "planes", lambda cell: planes)
    run = record(planes, work=0 if missing == "frames" else 4)
    assert read(READER, run) is None


def test_reads_a_cpu_trace_of_reduce_frames(monkeypatch, tmp_path):
    """``reduce_frames`` traced inside the window where the harness keeps
    the cell's trace: one ``hedm.label`` span a frame, and the reader is
    their seconds over the frames."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.hedm.pipeline import reduce_frames, simulate_detector_frames
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    frames, dark = simulate_detector_frames(3, size=64, n_spots=4, seed=7)
    frames, dark = frames.astype(np.uint16), dark.astype(np.uint16)
    reduce_frames(frames, dark, use_kernel=False)            # compiled
    jax.profiler.start_trace(str(tmp_path / CELL))
    try:
        with TraceAnnotation(harness.WINDOW_SPAN):
            for _ in range(2):
                reduce_frames(frames, dark, use_kernel=False)
    finally:
        jax.profiler.stop_trace()
    run = record(host_spans.planes(CELL), work=6)
    spans = host_spans.of(run)
    assert len(spans["hedm.label"]) == 6
    value = read(READER, run)
    assert value > 0
    assert value == pytest.approx(
        1e3 * host_spans.seconds(spans, "hedm.label") / 6)
