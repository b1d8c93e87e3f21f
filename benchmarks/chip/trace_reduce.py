"""Reduction of a JAX profiler trace to device busy time, idle share,
module time, top operations and idle gaps attributed to host spans.

A trace is read from the ``.xplane.pb`` file that ``jax.profiler`` writes.
Each TPU is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
one event per device operation and its ``XLA Modules`` line one event per
executed program. The benchmark's own spans (``jax.profiler
.TraceAnnotation`` names starting with ``bench.``) are read from the host
plane, with the runtime's host-to-device and device-to-host transfers;
the span named by ``window`` bounds the measured window, and every device
number is taken inside it, widened by ``DEVICE_SLACK_NS`` on each side:
the device's timestamps are put on the host's clock with an error of about
a millisecond (an op can appear to start before the host dispatched it).
Ops nest (a ``while`` holds its body's ops), so
device time is always a union of intervals, never a sum.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
NO_SPAN = "no bench span"
DEVICE_SLACK_NS = 5_000_000
RUNTIME_SPANS = {                       # runtime host events -> activity
    "tpu::System::TransferToDevice": "host-to-device copy",
    "H2D Dispatch": "host-to-device copy",
    "tpu::System::TransferFromDevice": "device-to-host copy",
    "D2H Dispatch": "device-to-host copy",
    "np.asarray(jax.Array)": "device-to-host copy",
}


@dataclass
class Events:
    """Events of one line: ``start``/``end`` in ns, ``name`` and the
    program (``module``) each belongs to."""
    start: np.ndarray
    end: np.ndarray
    name: List[str]
    module: List[str]

    @classmethod
    def of(cls, rows) -> "Events":
        rows = sorted(rows)
        return cls(np.array([r[0] for r in rows], np.int64),
                   np.array([r[1] for r in rows], np.int64),
                   [r[2] for r in rows], [r[3] for r in rows])


@dataclass
class Profile:
    """A trace reduced to what the benchmark reads."""
    ops: Dict[int, Events]                 # device id -> XLA ops
    modules: Dict[int, Events]             # device id -> executed programs
    spans: List[Tuple[int, int, str]]      # host spans of the benchmark
    window: Tuple[int, int]                # the measured window, ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def device_window(self) -> Tuple[int, int]:
        return (self.window[0] - DEVICE_SLACK_NS,
                self.window[1] + DEVICE_SLACK_NS)


def _module_name(name: str) -> str:
    """``jit_hedm_reduce(123)`` -> ``jit_hedm_reduce``."""
    return re.sub(r"\(\d+\)$", "", name)


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def profile_from_planes(planes, window: str = "bench.window") -> Profile:
    """Build a :class:`Profile` from planes of ``jax.profiler.ProfileData``
    (or objects of the same shape: ``name``, ``lines``; lines with
    ``name``, ``events``; events with ``name``, ``start_ns``,
    ``duration_ns``, ``stats``)."""
    ops, modules, spans = {}, {}, []
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            lines = {line.name: list(line.events) for line in plane.lines}
            mod_rows = [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                         _module_name(e.name), _module_name(e.name))
                        for e in lines.get(MODULES_LINE, [])]
            modules[dev] = Events.of(mod_rows)
            op_rows = []
            for e in lines.get(OPS_LINE, []):
                s = int(e.start_ns)
                module = _stats(e).get("hlo_module")
                op_rows.append([s, int(s + e.duration_ns),
                                e.name.split(" = ")[0], module])
            _assign_modules(op_rows, modules[dev])
            ops[dev] = Events.of([tuple(r) for r in op_rows])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = (e.name if e.name.startswith(SPAN_PREFIX)
                            else RUNTIME_SPANS.get(e.name))
                    if name:
                        s = int(e.start_ns)
                        spans.append((s, int(s + e.duration_ns), name))
    spans.sort()
    windows = [(s, e) for s, e, n in spans if n == window]
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} spans named {window!r}, "
                         f"expected 1")
    if not ops:
        raise ValueError("trace holds no TPU plane")
    return Profile(ops, modules, [s for s in spans if s[2] != window],
                   windows[0])


def _assign_modules(op_rows, modules: Events) -> None:
    """Give an op without an ``hlo_module`` stat the program whose
    execution contains its start."""
    for r in op_rows:
        if r[3]:
            r[3] = _module_name(str(r[3]))
            continue
        i = int(np.searchsorted(modules.start, r[0], side="right")) - 1
        r[3] = (modules.name[i] if i >= 0 and modules.end[i] >= r[0]
                else "?")


def load(path: str, window: str = "bench.window") -> Profile:
    from jax.profiler import ProfileData
    return profile_from_planes(ProfileData.from_file(str(path)).planes,
                               window)


def merge(start: np.ndarray, end: np.ndarray, lo: int, hi: int):
    """Union of intervals clipped to ``[lo, hi]``, as sorted disjoint
    ``(start, end)`` arrays."""
    s = np.clip(start, lo, hi)
    e = np.clip(end, lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if not len(s):
        return s, e
    order = np.argsort(s, kind="stable")
    s, e = s[order], np.maximum.accumulate(e[order])
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > e[:-1]
    idx = np.flatnonzero(new)
    ends = np.append(idx[1:] - 1, len(s) - 1)
    return s[idx], e[ends]


def busy_ns(profile: Profile, device: int) -> int:
    ev = profile.ops[device]
    s, e = merge(ev.start, ev.end, *profile.device_window)
    return int(np.sum(e - s))


def busy_s(profile: Profile) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    return float(np.mean([busy_ns(profile, d) for d in profile.ops])) * 1e-9


def idle_share(profile: Profile) -> float:
    """1 - busy / window, averaged over the devices."""
    return 1.0 - busy_s(profile) / profile.window_s


def _in_window(ev: Events, window) -> np.ndarray:
    return (ev.start >= window[0]) & (ev.end <= window[1])


def module_time(profile: Profile, module: str) -> Tuple[float, int]:
    """Device seconds in which an op of program ``module`` ran inside the
    window, and how many times the program ran there, over all devices."""
    secs, runs = 0, 0
    for dev, ev in profile.ops.items():
        mine = np.array([m == module for m in ev.module], bool)
        if mine.any():
            s, e = merge(ev.start[mine], ev.end[mine],
                         *profile.device_window)
            secs += int(np.sum(e - s))
        mods = profile.modules[dev]
        if len(mods.start):
            runs += int(np.sum(_in_window(mods, profile.device_window)
                               & np.array([n == module for n in mods.name])))
    return secs * 1e-9, runs


def top_ops(profile: Profile, n: int = 10) -> List[List]:
    """The ``n`` device ops that took most time in the window, as
    ``[module:op, seconds]`` averaged over the devices (a ``while`` op and
    the ops of its body are listed each)."""
    acc: Dict[str, int] = defaultdict(int)
    for ev in profile.ops.values():
        sel = np.flatnonzero(_in_window(ev, profile.device_window))
        for i in sel:
            acc[f"{ev.module[i]}:{ev.name[i]}"] += int(ev.end[i] - ev.start[i])
    k = len(profile.ops)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9 / k] for name, ns in rows]


def _innermost_timeline(spans, lo: int, hi: int):
    """Cut ``[lo, hi]`` into segments, each named by the innermost span
    open in it (the one that started last)."""
    bounds = sorted({lo, hi, *(t for s, e, _ in spans for t in (s, e)
                               if lo < t < hi)})
    starts = np.array([s for s, _, _ in spans], np.int64)
    ends = np.array([e for _, e, _ in spans], np.int64)
    seg_s, seg_e, seg_n = [], [], []
    for a, b in zip(bounds[:-1], bounds[1:]):
        open_ = np.flatnonzero((starts <= a) & (ends >= b))
        name = spans[open_[np.argmax(starts[open_])]][2] if len(open_) \
            else NO_SPAN
        seg_s.append(a)
        seg_e.append(b)
        seg_n.append(name)
    return np.array(seg_s, np.int64), np.array(seg_e, np.int64), seg_n


def idle_gaps(profile: Profile, n: int = 10) -> List[List]:
    """Idle device time in the window, summed by the innermost benchmark
    span the host was in meanwhile, as the ``n`` largest
    ``[span, seconds]`` averaged over the devices."""
    lo, hi = profile.window
    seg_s, seg_e, seg_n = _innermost_timeline(profile.spans, lo, hi)
    acc: Dict[str, int] = defaultdict(int)
    for ev in profile.ops.values():
        bs, be = merge(ev.start, ev.end, *profile.device_window)
        bs, be = np.clip(bs, lo, hi), np.clip(be, lo, hi)
        gap_s = np.concatenate([[lo], be])
        gap_e = np.concatenate([bs, [hi]])
        for a, b in zip(gap_s, gap_e):
            if b <= a:
                continue
            i = max(int(np.searchsorted(seg_e, a, side="right")), 0)
            while i < len(seg_s) and seg_s[i] < b:
                acc[seg_n[i]] += int(min(b, seg_e[i]) - max(a, seg_s[i]))
                i += 1
    k = len(profile.ops)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9 / k] for name, ns in rows]

