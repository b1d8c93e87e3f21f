"""``mask_dense_share``: the frames whose mask came back whole, over all
frames the copies back brought, on synthetic host spans of the one-chip
and the four-chip copy back."""
from types import SimpleNamespace as NS

import pytest

import harness
import host_spans

MS = 1_000_000                     # ns


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start * MS, duration_ns=dur * MS,
              stats=stats)


def planes(*spans):
    """Window [100, 1100] ms holding ``spans``; one copy back before the
    window, of 4 frames all whole, is left out."""
    events = [ev("bench.window", 100, 1000),
              ev("hedm.from_device", 50, 10, bytes=9, frames=4,
                 dense_frames=4), *spans]
    return [NS(name="/host:CPU", lines=[NS(name="python3", events=events)])]


def read(monkeypatch, planes_):
    monkeypatch.setattr(host_spans, "planes", lambda cell: planes_)
    run = NS(cell=NS(name="nf_hedm_736.scan_w16", config={}),
             profile=NS(window=(100 * MS, 1100 * MS)))
    return harness.load_module(
        harness.HERE / "metrics" / "mask_dense_share.py").read(run)


@pytest.mark.parametrize("name,extra", [("hedm.from_device", {}),
                                        ("hedm.from_devices", {"chips": 4})])
def test_share_of_frames_that_came_back_whole(monkeypatch, name, extra):
    spans = [ev(name, 200 + 100 * i, 10, bytes=1000, frames=16,
                dense_frames=d, **extra) for i, d in enumerate((0, 3, 1))]
    assert read(monkeypatch, planes(*spans)) == pytest.approx(
        100 * 4 / 48)


def test_reads_zero_when_no_frame_came_back_whole(monkeypatch):
    spans = [ev("hedm.from_device", 200 + 100 * i, 10, bytes=1000,
                frames=16, dense_frames=0) for i in range(3)]
    value = read(monkeypatch, planes(*spans))
    assert value == 0.0 and value is not None


@pytest.mark.parametrize("spans", [
    [],                                                  # no copy spans
    [ev("hedm.from_device", 200, 10, bytes=64 << 20)],   # dense copies only
])
def test_reports_nothing_without_frame_counts(monkeypatch, spans):
    assert read(monkeypatch, planes(*spans)) is None
