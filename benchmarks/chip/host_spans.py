"""The program's own host spans (``hedm.*``) in a traced run.

``repro.hedm.pipeline.reduce_frames`` marks its steps with
``jax.profiler.TraceAnnotation``\\ s named ``hedm.*`` on the profiler's
host plane; its copies' spans carry the bytes they moved as the stat
``bytes``. They are read from the trace file that the harness wrote for
the run, inside the run's measured window. A program without such spans
gives none, and its readers report nothing.
"""
from __future__ import annotations

from collections import defaultdict
from functools import lru_cache
from typing import Dict, List, Tuple

import harness
from trace_reduce import _stats

PREFIX = "hedm."

Spans = Dict[str, List[Tuple[int, dict]]]     # name -> (duration ns, stats)


def planes(cell: str) -> list:
    """Planes of the newest trace of ``cell``; none when it has no trace."""
    found = sorted((harness.TRACE_DIR / cell).glob(
        "plugins/profile/*/*.xplane.pb"))
    if not found:
        return []
    return _load(str(found[-1]), found[-1].stat().st_mtime_ns).planes


@lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int):
    """The trace at ``path``, parsed once for all of a run's readers."""
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def from_planes(planes_, window: Tuple[int, int]) -> Spans:
    """Host events named ``hedm.*`` that lie inside ``window`` (ns)."""
    lo, hi = window
    spans: Spans = defaultdict(list)
    for plane in planes_:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith(PREFIX):
                    continue
                s, d = int(e.start_ns), int(e.duration_ns)
                if lo <= s and s + d <= hi:
                    spans[e.name].append((d, _stats(e)))
    return dict(spans)


def of(run) -> Spans:
    """The ``hedm.*`` spans inside the window of ``run`` (a ``RunRecord``)."""
    return from_planes(planes(run.cell.name), run.profile.window)


def seconds(spans: Spans, *names: str) -> float:
    """Summed seconds of the spans named ``names``."""
    return 1e-9 * sum(d for n in names for d, _ in spans.get(n, ()))


def gb_per_s(spans: Spans, name: str):
    """Bytes over seconds of the spans named ``name``, in GB/s; ``None``
    without such spans or their byte counts."""
    rows = spans.get(name, ())
    moved = sum(int(st.get("bytes", 0)) for _, st in rows)
    secs = seconds(spans, name)
    if not moved or not secs or any("bytes" not in st for _, st in rows):
        return None
    return moved / secs * 1e-9
