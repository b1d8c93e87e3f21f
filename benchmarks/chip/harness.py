"""Runs one benchmark cell once and prints its result line.

Everything that belongs to one cell is found by name:

- ``BENCHMARK.json`` at the checkout's root lists the cell, its
  configuration, and which metrics it reports;
- ``configs/<config>.json`` holds the configuration's sizes;
- ``cells/<cell>.json`` names the driver that generates the cell's
  traffic (``drivers/<driver>.py``), the traffic's parameters, the
  end-to-end rate the driver's work counts toward, and the parameters
  (``compare``) and limits (``checks``) of the comparison that decides
  ``correct``;
- ``metrics/<metric>.py`` reads one per-layer metric from a traced run.

A driver module has four functions: ``setup(ctx)`` makes the inputs from
the seed and warms every shape the window uses; ``window(ctx, state,
seconds)`` drives the program for that long and returns a
:class:`Window`; ``release(state)`` frees the program's device state;
``check(ctx, state, window)`` compares the window's outputs with the
reference and returns :class:`Check` rows.
"""
from __future__ import annotations

import contextlib
import ctypes
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_traces"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
WINDOW_SPAN = "bench.window"
# glibc's mallopt parameters, and the values the benchmark pins them to
M_TRIM_THRESHOLD, M_MMAP_THRESHOLD = -1, -3
MMAP_THRESHOLD_BYTES = 32 << 20     # the ceiling of glibc's dynamic threshold
TRIM_THRESHOLD_BYTES = 1 << 30      # far above what one call frees


class BenchError(RuntimeError):
    """The cell cannot be run as asked."""


@dataclass
class Check:
    """One number compared with the reference, and its limit (the number
    passes while it is at most the limit)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit     # NaN fails


@dataclass
class Window:
    """What a driver's measured window did."""
    work: int                  # frames, fits, ...: the rate's numerator
    failed: int
    elapsed: float             # seconds from the window's start to its end
    calls: int                 # calls into the program's entry
    outputs: object = None     # what ``check`` compares


@dataclass
class Cell:
    name: str
    config: dict
    spec: dict                 # the cell file
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def traffic(self) -> dict:
        return self.spec["traffic"]

    @property
    def limits(self) -> Dict[str, float]:
        return self.spec["checks"]


@dataclass
class Context:
    cell: Cell
    seed: int
    trace: bool
    timers: Dict[str, List[float]] = field(default_factory=dict)

    @property
    def config(self) -> dict:
        return self.cell.config

    def span(self, name: str):
        """A host span around a call into one layer, written into the
        profiler's trace in traced runs."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def timed(self, name: str, fn: Callable) -> Callable:
        """``fn`` under a span, its host-clock seconds added to
        ``timers[name]``."""
        acc = self.timers.setdefault(name, [])

        def wrapper(*args, **kwargs):
            with self.span(name):
                t0 = time.perf_counter()
                try:
                    return fn(*args, **kwargs)
                finally:
                    acc.append(time.perf_counter() - t0)
        return wrapper


@dataclass
class RunRecord:
    """What a per-layer metric reader gets."""
    cell: Cell
    window: Window
    profile: object            # trace_reduce.Profile
    timers: Dict[str, List[float]]
    compiles: int              # backend compile events inside the window
    peaks: dict


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    name = "bench_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> Cell:
    spec = load_json(ROOT / "BENCHMARK.json")
    entries = [w for w in spec["workloads"] if w["name"] == name]
    if not entries:
        raise BenchError(f"no workload named {name!r} in BENCHMARK.json")
    entry = entries[0]
    cfg = [c for c in spec["configs"] if c["name"] == entry["config"]][0]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"]
                                  in e2e_names else [])]
    return Cell(name=name, config=load_json(ROOT / cfg["file"]),
                spec=load_json(HERE / "cells" / f"{name}.json"),
                chips=entry["chips"], end_to_end=e2e, per_layer=per_layer)


def accelerator(chips: int):
    """The chips this cell runs on; no TPU, or too few, is an error."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs


def pin_allocator() -> None:
    """Fix the C allocator's mmap and trim thresholds for the whole process.

    glibc moves both as the process frees large blocks, so the speed of
    the program's host numpy (hundreds of MB of temporaries a call) would
    depend on what the process did before: whether it compiled its
    programs or fetched them from the persistent cache. Pinned, blocks up
    to the dynamic ceiling come from the heap and freed memory stays
    there for the next call, as in a long-lived process."""
    mallopt = getattr(ctypes.CDLL(None), "mallopt", None)
    if mallopt is None or not (
            mallopt(M_MMAP_THRESHOLD, MMAP_THRESHOLD_BYTES)
            and mallopt(M_TRIM_THRESHOLD, TRIM_THRESHOLD_BYTES)):
        raise BenchError("cannot pin the C allocator: no glibc mallopt")


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    when that is set, else at one fixed path in the checkout; every
    program is kept, so that a second run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compilations (each a compile or a fetch from the
    persistent cache) through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1


def _memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _trace_file(log_dir: Path) -> Path:
    found = sorted(log_dir.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise BenchError(f"the profiler wrote no trace under {log_dir}")
    return found[-1]


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float, cell: Optional[Cell] = None,
             require_chip: bool = True) -> dict:
    """Run cell ``name`` once; returns the result object. ``t_start`` is
    the host clock at which the process began its set-up. Tests pass a
    reduced ``cell`` and ``require_chip=False``."""
    cell = cell or load_cell(name)
    import jax
    devices = (accelerator(cell.chips) if require_chip
               else jax.devices())[:cell.chips]
    use_compile_cache()
    compiles = CompileCounter()
    driver = load_module(HERE / "drivers" / f"{cell.spec['driver']}.py")
    ctx = Context(cell=cell, seed=seed, trace=trace)

    state = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start

    log_dir = TRACE_DIR / cell.name
    if trace:
        shutil.rmtree(log_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(log_dir), profiler_options=options)
    compiles_before = compiles.count
    with ctx.span(WINDOW_SPAN):
        win = driver.window(ctx, state, seconds)
    compiles_in_window = compiles.count - compiles_before
    if trace:
        jax.profiler.stop_trace()
    memory_peak = _memory_peak(devices)

    driver.release(state)
    gc.collect()
    checks = driver.check(ctx, state, win)

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": all(c.ok for c in checks), "attempted": win.work,
              "failed": win.failed}
    if trace:
        import trace_reduce
        from costs import peaks
        profile = trace_reduce.load(_trace_file(log_dir), WINDOW_SPAN)
        record = RunRecord(cell=cell, window=win, profile=profile,
                           timers=ctx.timers, compiles=compiles_in_window,
                           peaks=peaks(d0.device_kind) if require_chip
                           else {})
        metrics = {}
        for m in cell.per_layer:
            value = load_module(HERE / "metrics" / f"{m['name']}.py").read(
                record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = trace_reduce.busy_s(profile)
        device["window_s"] = profile.window_s
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(profile),
            "idle_gaps": trace_reduce.idle_gaps(profile)}
    else:
        values = {"setup_s": setup_s,
                  cell.spec["rate_metric"]: win.work / win.elapsed}
        missing = [m["name"] for m in cell.end_to_end
                   if m["name"] not in values]
        if missing:
            raise BenchError(f"cell {cell.name} cannot report {missing}")
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = {c.name: {"value": _number(c.value), "limit": c.limit}
                        for c in checks}
    return result


def _number(x):
    """A number as JSON can hold it: a non-finite one as its name."""
    return x if math.isfinite(x) else str(x)


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        pin_allocator()
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start)
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        ok = isinstance(c["value"], (int, float)) and c["value"] <= c["limit"]
        verdict = "ok" if ok else "FAILED"
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}) "
              f"{verdict}", file=sys.stderr)
    print(f"correct: {str(result['correct']).lower()}", file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 0
