"""Stage-1 traffic on one host of several chips: whole scans, one after
another, each staged into every chip's HBM and reduced by all of them (a
closed loop).

The scan is rendered from the seed and held on the host, as for the
one-chip ``scan`` driver. Each pass hands it to the program's
``stage_scan`` (a share of the frames to each chip, then an all-gather, so
that every chip holds the whole scan) and reduces it with
``reduce_resident``, every chip filtering its own share from its replica.
The previous pass's replica is dropped before the next is staged, so no
two live at once; the last one staged in the window is kept for the check.
The window counts every frame whose peak list came back.

The check is the ``scan`` driver's (counts of every frame returned, peaks
of a sample, against the same reference), and a check of the replicas
that imports nothing of the program: for every frame on every chip, two
exact integer checksums, the sum of the values and the sum of the values
weighted by their position in the frame, computed on that chip with
``jax.numpy`` and compared with the same checksums of the host scan in
numpy.
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import harness
import scangen
from harness import BenchError, Check, Window

AXIS = "data"


def _program():
    """The program's staging and resident stage-1 entries; a program
    without them cannot run the cell, which fails before any set-up."""
    from repro.hedm import pipeline
    missing = [n for n in ("stage_scan", "reduce_resident")
               if not hasattr(pipeline, n)]
    if missing:
        raise BenchError(f"the program has no {', '.join(missing)}: it "
                         f"cannot stage a scan onto the chips")
    return pipeline


def setup(ctx):
    cfg, tr = ctx.config, ctx.cell.traffic
    pipeline = _program()
    mesh = Mesh(np.array(jax.devices()[:ctx.cell.chips]), (AXIS,))
    scan, dark = scangen.render_scan(cfg, ctx.seed)
    resident = pipeline.stage_scan(mesh, scan, dark, AXIS)
    for _ in pipeline.reduce_resident(resident, window=tr["window"],
                                      threshold=cfg["threshold"]):
        pass                     # every step's shape, the ragged one too
    return {"scan": scan, "dark": dark, "mesh": mesh, "resident": None}


def window(ctx, state, seconds):
    cfg, tr = ctx.config, ctx.cell.traffic
    pipeline = _program()
    scan, dark, mesh = state["scan"], state["dark"], state["mesh"]
    chips, w = ctx.cell.chips, tr["window"]
    share = len(scan) // chips
    answers, missing, calls = [], 0, 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    done = False
    while not done:
        state["resident"] = None                  # one replica at a time
        with ctx.span("bench.stage"):
            state["resident"] = pipeline.stage_scan(mesh, scan, dark, AXIS)
        calls += 1
        steps = pipeline.reduce_resident(state["resident"], window=w,
                                         threshold=cfg["threshold"])
        for first in range(0, share, w):
            with ctx.span("bench.reduce_frames"):
                chunk = next(steps, None)
            calls += 1
            due = {i * share + f for i in range(chips)
                   for f in range(first, min(first + w, share))}
            got = [r.frame_id for r in chunk or []]
            missing += len(due - set(got)) + len(got) - len(due & set(got))
            answers.extend(chunk or [])
            if time.perf_counter() >= deadline:
                done = True
                break
        steps.close()
    elapsed = time.perf_counter() - t0
    return Window(work=len(answers), failed=missing, elapsed=elapsed,
                  calls=calls, outputs=answers)


def release(state):
    """Nothing to free before the check: it reads the last replica."""


@jax.jit
def _line_sums(frames):
    """Per frame, the sum of each row and of each column, exact in int32
    (a line of 2048 uint16 values stays below 2**31)."""
    return (jnp.sum(frames, axis=2, dtype=jnp.int32),
            jnp.sum(frames, axis=1, dtype=jnp.int32))


def checksums(rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
    """``(F, 2)`` int64: per frame the sum of its values and the sum of each
    value times its position ``y * W + x``, from its row and column sums."""
    rows, cols = rows.astype(np.int64), cols.astype(np.int64)
    y = np.arange(rows.shape[1], dtype=np.int64)
    x = np.arange(cols.shape[1], dtype=np.int64)
    weighted = rows @ (y * cols.shape[1]) + cols @ x
    return np.stack([rows.sum(axis=1), weighted], axis=1)


def replica_mismatches(replica, host: np.ndarray, chips: int) -> int:
    """Frames, counted over every chip, whose replica's checksums differ
    from the host scan's; a chip without the whole scan counts every
    frame."""
    want = checksums(host.sum(axis=2, dtype=np.int64),
                     host.sum(axis=1, dtype=np.int64))
    if replica is None:
        return chips * len(host)
    shards = {s.device.id: s.data for s in replica.addressable_shards}
    bad = (chips - len(shards)) * len(host)
    for data in shards.values():
        if data.shape != host.shape:
            bad += len(host)
            continue
        rows, cols = _line_sums(data)
        got = checksums(np.asarray(rows), np.asarray(cols))
        bad += int(np.sum(np.any(got != want, axis=1)))
    return bad


def check(ctx, state, win):
    resident = state.pop("resident", None)
    bad = replica_mismatches(None if resident is None else resident.frames,
                             state["scan"], ctx.cell.chips)
    del resident
    scan_driver = harness.load_module(harness.HERE / "drivers" / "scan.py")
    rows = scan_driver.check(ctx, state, win)
    name = "replica_mismatch_frames"
    return rows + [Check(name, bad, ctx.cell.limits[name])]
