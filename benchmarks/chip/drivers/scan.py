"""Stage-1 traffic: whole scans, one after another, through
``reduce_frames_online`` (a closed loop).

The scan is rendered from the seed on the device and held on the host as
the detector delivers it. The window counts every frame whose peak list
came back. The check compares, for every frame returned, the signal-pixel
count with the reference filter's, and for a sample of frames drawn from
the seed (with the frame of most signal in it) the spot count and every
peak's centroid and intensity.
"""
from __future__ import annotations

import time

import numpy as np

import jax.numpy as jnp

import ref_stage1
import scangen
from harness import Check, Window

STREAM_SAMPLE = 4


def _program():
    from repro.hedm import pipeline
    from repro.kernels import ops
    return pipeline, ops


def setup(ctx):
    cfg, tr = ctx.config, ctx.cell.traffic
    scan, dark = scangen.render_scan(cfg, ctx.seed)
    pipeline, ops = _program()
    w, n = tr["window"], len(scan)
    for length in sorted({w, n % w} - {0}):      # every window length
        for _ in pipeline.reduce_frames_online(
                scan[:length], dark, window=length,
                threshold=cfg["threshold"]):
            pass
    state = {"scan": scan, "dark": dark, "restore": []}
    if ctx.trace:
        for mod, attr, span in ((pipeline, "label_components", "bench.label"),
                                (ops, "hedm_reduce", "bench.hedm_reduce")):
            fn = getattr(mod, attr)
            state["restore"].append((mod, attr, fn))
            setattr(mod, attr, ctx.timed(span, fn))
    return state


def window(ctx, state, seconds):
    cfg, tr = ctx.config, ctx.cell.traffic
    pipeline, _ = _program()
    scan, dark = state["scan"], state["dark"]
    w, n = tr["window"], len(scan)
    answers, missing, calls = [], 0, 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    done = False
    while not done:
        chunks = pipeline.reduce_frames_online(
            scan, dark, window=w, threshold=cfg["threshold"])
        for w0 in range(0, n, w):
            with ctx.span("bench.reduce_frames"):
                chunk = next(chunks, None)
            calls += 1
            due = set(range(w0, min(w0 + w, n)))
            got = [r.frame_id for r in chunk or []]
            missing += len(due - set(got)) + len(got) - len(due & set(got))
            answers.extend(chunk or [])
            if time.perf_counter() >= deadline:
                done = True
                break
        chunks.close()
    elapsed = time.perf_counter() - t0
    return Window(work=len(answers), failed=missing, elapsed=elapsed,
                  calls=calls, outputs=answers)


def release(state):
    for mod, attr, fn in state["restore"]:
        setattr(mod, attr, fn)
    state["restore"] = []


def check(ctx, state, win):
    cfg, limits = ctx.config, ctx.cell.limits
    compare = ctx.cell.spec["compare"]
    scan, dark = state["scan"], state["dark"]
    thr = cfg["threshold"]
    answers = win.outputs
    ids = sorted({r.frame_id for r in answers if 0 <= r.frame_id < len(scan)})
    dark_d = jnp.asarray(dark)
    counts = {}
    for part, _, c in ref_stage1.filter_batches(scan, dark_d, ids, thr):
        counts.update(zip(part, c[:len(part)].tolist()))
    rng = np.random.default_rng([ctx.seed % 2**64, STREAM_SAMPLE])
    busiest = max(ids, key=lambda f: counts[f])
    rest = [f for f in ids if f != busiest]
    k = min(compare["sample_frames"] - 1, len(rest))
    sample = sorted([busiest, *rng.choice(rest, k, replace=False).tolist()])
    peaks = {}
    for part, masks, _ in ref_stage1.filter_batches(scan, dark_d, sample,
                                                    thr):
        masks = np.asarray(masks)
        for j, f in enumerate(part):
            peaks[f] = ref_stage1.peak_list(masks[j] > 0, scan[f])
    del dark_d

    count_bad = spot_bad = 0
    pos_gap = rel_gap = 0.0
    for r in answers:
        if counts.get(r.frame_id) != r.n_signal_pixels:
            count_bad += 1
        want = peaks.get(r.frame_id)
        if want is None:
            continue
        if r.n_spots != len(want) or len(r.peaks) != len(want):
            spot_bad += 1
            continue
        p, q = ref_stage1.peak_gaps(r.peaks, want)
        pos_gap, rel_gap = max(pos_gap, p), max(rel_gap, q)
    values = {"frames_missing": win.failed,
              "count_mismatch_frames": count_bad,
              "spot_count_mismatch_frames": spot_bad,
              "centroid_gap_px": pos_gap,
              "intensity_gap_rel": rel_gap}
    return [Check(name, values[name], limits[name]) for name in values]
