"""Trace reduction: device busy union, idle share, module time, top ops and
idle gaps attributed to the innermost host span; on synthetic planes and on
a small trace recorded on a TPU v5e."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import trace_reduce as tr

MS = 1_000_000                     # ns
RECORDED = Path(__file__).parent / "data" / "v5e_reduce_and_fit.xplane.pb"


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start * MS, duration_ns=dur * MS,
              stats=stats)


def line(name, *events):
    return NS(name=name, events=list(events))


def synthetic():
    """Window [100, 1100] ms. Device ops: A (module m1) 99-300, starting
    1 ms before the window (inside the clock slack); B (m1) 250-400,
    overlapping A; C (m2, module from the Modules line) 700-800, holding a
    nested op C1 710-790; D (m1) 1050-1200, crossing the window's end by
    more than the slack. Host spans: call 100-500 with label 320-480 inside
    it, call 600-900, and a runtime copy 850-950."""
    device = NS(name="/device:TPU:0", lines=[
        line("XLA Modules", ev("m1(7)", 99, 301), ev("m2(9)", 690, 120),
             ev("m1(7)", 1040, 200)),
        line("XLA Ops", ev("A = fusion(x)", 99, 201, hlo_module="m1"),
             ev("B", 250, 150, hlo_module="m1"), ev("C", 700, 100),
             ev("C1", 710, 80), ev("D", 1050, 150, hlo_module="m1"))])
    host = NS(name="/host:CPU", lines=[
        line("python3", ev("bench.window", 100, 1000),
             ev("bench.call", 100, 400), ev("bench.label", 320, 160),
             ev("bench.call", 600, 300), ev("other", 0, 5000)),
        line("runtime", ev("tpu::System::TransferFromDevice", 850, 100))])
    return tr.profile_from_planes([NS(name="/host:metadata", lines=[]),
                                   device, host])


def test_busy_union_and_idle_share():
    p = synthetic()
    # union: 99-400 (A u B), 700-800 (C holds C1), 1050-1105 (D, to the
    # window's end plus the slack)
    assert tr.busy_ns(p, 0) == (301 + 100 + 55) * MS
    assert p.window_s == pytest.approx(1.0)
    assert tr.busy_s(p) == pytest.approx(0.456)
    assert tr.idle_share(p) == pytest.approx(1 - 0.456)


def test_module_time_is_a_union_inside_the_window():
    p = synthetic()
    secs, runs = tr.module_time(p, "m1")
    assert secs == pytest.approx(0.301 + 0.055) and runs == 1
    secs, runs = tr.module_time(p, "m2")
    assert secs == pytest.approx(0.100) and runs == 1


def test_top_ops_and_idle_gaps_by_innermost_span():
    p = synthetic()
    assert tr.top_ops(p, 3) == [["m1:A", pytest.approx(0.201)],
                                ["m1:B", pytest.approx(0.150)],
                                ["m2:C", pytest.approx(0.100)]]
    gaps = dict(tr.idle_gaps(p))
    # idle 400-700: label 400-480, call 480-500, none 500-600, call 600-700;
    # idle 800-1050: call 800-850, copy 850-950, none 950-1050
    assert gaps == {"bench.label": pytest.approx(0.080),
                    "bench.call": pytest.approx(0.170),
                    "device-to-host copy": pytest.approx(0.100),
                    tr.NO_SPAN: pytest.approx(0.200)}


def test_a_trace_without_window_or_device_is_refused():
    host = NS(name="/host:CPU", lines=[line("t", ev("bench.window", 0, 9))])
    with pytest.raises(ValueError):
        tr.profile_from_planes([host])
    with pytest.raises(ValueError):
        tr.profile_from_planes([NS(name="/device:TPU:0", lines=[])])


def test_recorded_v5e_trace():
    """Two ``hedm_reduce`` calls on 4x256x256 uint16 frames and one
    ``fit_grid`` of 64 points inside ``bench.window``, traced on one v5e.
    By hand from the trace's ``XLA Modules`` line: the two hedm runs took
    27,660 and 27,883 ns, the fit's ``jit_scan`` 803,427 ns; its ``while``
    op holds the body's ops."""
    p = tr.load(RECORDED)
    assert list(p.ops) == [0]
    assert p.window_s == pytest.approx(0.627235742)
    assert tr.module_time(p, "jit_hedm_reduce")[1] == 2
    assert tr.module_time(p, "jit_scan") == (pytest.approx(803.1e-6, rel=1e-3),
                                             1)
    busy = tr.busy_s(p)
    assert 803e-6 < busy < 803e-6 + 2 * 27.9e-6
    assert tr.idle_share(p) == pytest.approx(1 - busy / p.window_s)
    assert tr.top_ops(p, 1)[0][0] == "jit_scan:%while"
    names = [s[2] for s in p.spans]
    assert names.count("bench.hedm_reduce") == 2
    assert names.count("bench.label") == 2
    assert {"bench.fit_grid", "bench.to_host", "host-to-device copy",
            "device-to-host copy"} <= set(names)
    # gaps are cut at the window itself, busy time at the window widened by
    # the clock slack: they differ by the first run, 27,660 ns, which the
    # device's clock puts before the window
    idle = sum(s for _, s in tr.idle_gaps(p, n=100))
    assert idle == pytest.approx(p.window_s - busy + 27.66e-6, abs=1e-6)
    assert dict(tr.idle_gaps(p))["bench.fit_grid"] > 0.5
