"""NF/FF-HEDM analysis pipeline (paper §II, §V, §VI).

Stage 0 — detector simulation: synthetic diffraction frames (bright spots on
noise, sparse like real frames) streamed to the shared FS (repro.core.fabric)
exactly as the APS detector writes to NFS/GPFS.

Stage 1 — data reduction (§VI-A): per-frame background subtraction, median
filter, Laplacian edge response, threshold, connected-component labeling ->
peak list. The filter half runs on the hedm_reduce kernel (or its jnp
oracle); labeling runs on host (networkx-free union-find).

Stage 2 — orientation fitting (§V-C, Fig. 8): for every grid point, fit the
crystal orientation (3 Euler-like params) to the observed diffraction
signature by batched Gauss-Newton — the FitOrientation() many-task stage,
vmapped/sharded instead of one C process per point.

Online mode — ``reduce_frames_online`` / ``run_online_hedm`` run stage-1
incrementally per sliding window over a streamed acquisition
(`repro.core.streaming`): results are produced while the detector is still
writing, and are bit-identical to the batch path (``run_batch_hedm``).

Interactive mode — ``run_interactive_hedm`` drives N concurrent analysis
sessions over M scans through the long-lived dataset catalog + staging
service (`repro.core.datasvc`): sessions lease datasets (coalescing
concurrent stages), reduce from the resident replicas, and write their
results back to the shared FS with the collective ``stage_out`` — the
"extended residency, various processing tasks" regime of §VI-B.
"""
from __future__ import annotations

import time as _time
from dataclasses import dataclass
from typing import (Dict, Iterator, List, NamedTuple, Optional, Sequence,
                    Tuple)

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.fabric import Fabric


# ---------------------------------------------------------------------------
# stage 0: detector simulation
# ---------------------------------------------------------------------------

def simulate_detector_frames(n_frames: int, size: int = 256,
                             n_spots: int = 12, seed: int = 0
                             ) -> Tuple[np.ndarray, np.ndarray]:
    """Synthetic diffraction frames: Gaussian spots on Poisson background.
    Returns (frames (F,size,size) float32, dark (size,size)).

    Spot rendering is fully vectorized: an isotropic Gaussian separates into
    a row factor and a column factor, so all F x n_spots spots render as one
    (F,S,H) x (F,S,W) einsum — no per-frame/per-spot Python loops.
    """
    rng = np.random.default_rng(seed)
    dark = rng.poisson(8.0, (size, size)).astype(np.float32)
    frames = rng.poisson(8.0, (n_frames, size, size)).astype(np.float32)
    if n_frames and n_spots:
        cy = rng.uniform(8, size - 8, (n_frames, n_spots, 1))
        cx = rng.uniform(8, size - 8, (n_frames, n_spots, 1))
        amp = rng.uniform(800, 4000, (n_frames, n_spots, 1))
        sig = rng.uniform(1.0, 2.5, (n_frames, n_spots, 1))
        r = np.arange(size, dtype=np.float64)
        gy = amp * np.exp(-((r - cy) ** 2) / (2 * sig ** 2))   # (F,S,H)
        gx = np.exp(-((r - cx) ** 2) / (2 * sig ** 2))         # (F,S,W)
        frames += np.einsum("fsh,fsw->fhw", gy, gx,
                            optimize=True).astype(np.float32)
    return frames, dark


def stream_to_fs(fabric: Fabric, frames: np.ndarray, prefix: str = "scan"
                 ) -> List[str]:
    """Detector -> shared FS, one file per frame (8 MB TIFFs in the paper)."""
    paths = []
    for i, frame in enumerate(frames):
        path = f"{prefix}/frame_{i:05d}.bin"
        fabric.fs.put(path, frame.astype(np.float32).view(np.uint8))
        paths.append(path)
    return paths


# ---------------------------------------------------------------------------
# stage 1: reduction
# ---------------------------------------------------------------------------

class Runs(NamedTuple):
    """Horizontal runs of a mask, in row-major order, with their final
    component labels: run ``i`` covers columns ``[col_s[i], col_e[i])`` of
    row ``row[i]`` and belongs to component ``label[i]`` (1..``n``)."""
    row: np.ndarray
    col_s: np.ndarray
    col_e: np.ndarray
    label: np.ndarray              # int32
    n: int                         # number of components


def label_runs(mask: np.ndarray) -> Runs:
    """Vectorized 4-connected component labeling of a mask's runs.

    Pass 1a finds horizontal runs of the whole mask at once (a sentinel
    column keeps runs from spanning rows); pass 1b unions runs that
    overlap between adjacent rows; the roots are then renumbered in scan
    order. Work is O(H*W) vectorized + O(#runs) scalar — for sparse
    diffraction masks #runs is ~100x smaller than #pixels.

    Label numbering matches ``_union_find_label`` exactly (components
    numbered by first pixel in row-major scan order).
    """
    H, W = mask.shape
    m = np.ascontiguousarray(mask, dtype=bool)
    if not m.any():
        none = np.zeros(0, np.int64)
        return Runs(none, none, none, np.zeros(0, np.int32), 0)

    # --- pass 1a: horizontal runs over the flattened mask -----------------
    padded = np.zeros((H, W + 1), bool)          # sentinel column: runs
    padded[:, :W] = m                            # never cross a row edge
    flat = padded.ravel()
    d = np.diff(flat.view(np.int8))
    starts = np.flatnonzero(d == 1) + 1
    ends = np.flatnonzero(d == -1) + 1           # every run closes (sentinel)
    if flat[0]:
        starts = np.concatenate(([0], starts))
    rows = starts // (W + 1)
    col_s = starts - rows * (W + 1)
    col_e = ends - rows * (W + 1)
    n_runs = len(starts)

    # --- pass 1b: union runs that overlap between adjacent rows ----------
    # Encode (row, col) into one monotone key so a SINGLE pair of
    # searchsorted calls finds, for every run i in row r, the contiguous
    # range [lo_i, hi_i) of row r-1 runs j with col_s[j] < col_e[i] and
    # col_e[j] > col_s[i] (4-connectivity overlap). Runs in other rows fall
    # outside [lo_i, hi_i) by key construction (row-0 runs get hi <= lo).
    stride = W + 2                               # > any col value
    key_s = rows * stride + col_s
    key_e = rows * stride + col_e
    target = (rows - 1) * stride
    lo = np.searchsorted(key_e, target + col_s, side="right")
    hi = np.searchsorted(key_s, target + col_e, side="left")
    n_ov = np.maximum(hi - lo, 0)
    pair_i = np.repeat(np.arange(n_runs), n_ov)
    off = np.concatenate(([0], n_ov.cumsum()[:-1]))
    pair_j = np.arange(n_ov.sum()) + np.repeat(lo - off, n_ov)

    parent = np.arange(n_runs, dtype=np.int64)

    def find(a: int) -> int:
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    for i, j in zip(pair_i.tolist(), pair_j.tolist()):
        ri, rj = find(i), find(j)
        if ri != rj:                         # min-root union keeps scan order
            if rj < ri:
                ri, rj = rj, ri
            parent[rj] = ri
    # full path compression, vectorized (log-depth)
    while True:
        p2 = parent[parent]
        if np.array_equal(p2, parent):
            break
        parent = p2

    # --- renumber roots in scan order -------------------------------------
    roots = np.unique(parent)                # sorted == first-run order
    run_label = (np.searchsorted(roots, parent) + 1).astype(np.int32)
    return Runs(rows, col_s, col_e, run_label, len(roots))


def run_pixels(runs: Runs) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Every pixel of ``runs`` as ``(y, x, label)``, in row-major order."""
    lengths = runs.col_e - runs.col_s
    first = np.concatenate(([0], lengths.cumsum()[:-1]))
    x = np.arange(lengths.sum()) + np.repeat(runs.col_s - first, lengths)
    return (np.repeat(runs.row, lengths), x,
            np.repeat(runs.label, lengths))


def run_centroids(runs: Runs, frame: np.ndarray) -> np.ndarray:
    """``(n, 3)`` float32 peak rows of each component of ``runs``:
    intensity-weighted centroid y, x and summed intensity of ``frame``,
    accumulated in float64.

    One bincount pass per moment over the runs' pixels only. The pixels
    come in row-major order, as a scan of a painted label image gives
    them, so the float64 sums are the ones that scan would give."""
    n = runs.n
    y, x, lab = run_pixels(runs)
    v = frame[y, x].astype(np.float64)
    s_i = np.bincount(lab, weights=v, minlength=n + 1)
    s_y = np.bincount(lab, weights=v * y, minlength=n + 1)
    s_x = np.bincount(lab, weights=v * x, minlength=n + 1)
    denom = np.maximum(s_i, 1e-9)
    return np.stack([s_y / denom, s_x / denom, s_i],
                    axis=1)[1:].astype(np.float32)


def label_components(mask: np.ndarray) -> Tuple[np.ndarray, int]:
    """4-connected component labels of ``mask`` as an ``(H, W)`` int32
    image and the component count: :func:`label_runs`, then one scatter
    paints each run's label.

    Label numbering matches ``_union_find_label`` exactly (components
    numbered by first pixel in row-major scan order), so the two are
    interchangeable; tests assert equivalence.
    """
    runs = label_runs(mask)
    y, x, lab = run_pixels(runs)
    out = np.zeros(mask.shape, np.int32)
    out[y, x] = lab
    return out, runs.n


def _union_find_label(mask: np.ndarray) -> Tuple[np.ndarray, int]:
    """Pure-Python pixel-loop 4-connected labeling. Kept as the reference
    oracle for :func:`label_components` (and the benchmark baseline) — the
    hot path uses the vectorized labeler."""
    H, W = mask.shape
    labels = np.zeros((H, W), np.int32)
    parent: List[int] = [0]

    def find(a):
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    nxt = 1
    for i in range(H):
        for j in range(W):
            if not mask[i, j]:
                continue
            up = labels[i - 1, j] if i else 0
            left = labels[i, j - 1] if j else 0
            if up and left:
                ru, rl = find(up), find(left)
                labels[i, j] = ru
                if ru != rl:
                    parent[max(ru, rl)] = min(ru, rl)
            elif up or left:
                labels[i, j] = up or left
            else:
                parent.append(nxt)
                labels[i, j] = nxt
                nxt += 1
    remap: Dict[int, int] = {}
    count = 0
    for i in range(H):
        for j in range(W):
            if labels[i, j]:
                r = find(labels[i, j])
                if r not in remap:
                    count += 1
                    remap[r] = count
                labels[i, j] = remap[r]
    return labels, count


@dataclass
class ReducedFrame:
    frame_id: int
    n_signal_pixels: int
    n_spots: int
    peaks: np.ndarray              # (n_spots, 3): y, x, intensity


def reduce_frames(frames: np.ndarray, dark: np.ndarray,
                  threshold: float = 200.0, use_kernel: bool = True
                  ) -> List[ReducedFrame]:
    """Stage-1 reduction of a frame stack (paper: 8 MB -> ~1 MB binary).

    Each step runs under a ``hedm.*`` span on the JAX profiler's host plane
    (free while no profiler trace is recording). The copy in and the filter
    wait for the device before their span ends, so that each span holds its
    own work, and the copies' spans carry the bytes they moved as the stat
    ``bytes``. Each frame's ``hedm.label`` span carries the runs the labeler
    found (stat ``runs``) and its ``hedm.centroids`` span the signal pixels
    whose moments it summed (stat ``pixels``): both follow the signal, not
    the frame size."""
    with TraceAnnotation("hedm.to_device") as span:
        frames_d, dark_d = jnp.asarray(frames), jnp.asarray(dark)
        jax.block_until_ready((frames_d, dark_d))
        span.set_metadata(bytes=frames_d.nbytes + dark_d.nbytes)
    with TraceAnnotation("hedm.filter"):
        if use_kernel:
            from repro.kernels.ops import hedm_reduce
            masks, counts = hedm_reduce(frames_d, dark_d,
                                        threshold=threshold)
        else:
            from repro.kernels.hedm_reduce_ref import reference
            masks, counts = reference(frames_d, dark_d, threshold=threshold)
        jax.block_until_ready((masks, counts))
    with TraceAnnotation("hedm.from_device") as span:
        masks = np.asarray(masks)
        counts = np.asarray(counts)
        span.set_metadata(bytes=masks.nbytes + counts.nbytes)
    # The pixels' (y, x) come from the labeler's runs, so this span holds no
    # work; it stays, once a call, because benchmarks/chip's centroid reader
    # and its tests still count it.
    with TraceAnnotation("hedm.index_grid"):
        pass
    out = []
    for f in range(frames.shape[0]):
        with TraceAnnotation("hedm.label") as span:
            runs = label_runs(masks[f] > 0)
            span.set_metadata(runs=len(runs.row))
        with TraceAnnotation("hedm.centroids") as span:
            peaks = run_centroids(runs, frames[f])
            span.set_metadata(pixels=int(np.sum(runs.col_e - runs.col_s)))
        out.append(ReducedFrame(f, int(counts[f]), runs.n, peaks))
    return out


# ---------------------------------------------------------------------------
# online (streaming) stage-1 mode
# ---------------------------------------------------------------------------

def reduce_frames_online(frames: np.ndarray, dark: np.ndarray,
                         window: int = 8, threshold: float = 200.0,
                         use_kernel: bool = True
                         ) -> Iterator[List[ReducedFrame]]:
    """Incremental stage-1: yield per-window ``ReducedFrame`` lists.

    The filter/label/centroid chain is per-frame independent, so splitting
    the frame axis into windows of `window` is bit-identical to one batch
    ``reduce_frames`` call over the whole stack (tests assert it); frame
    ids are global. This is the compute half of the online mode — the
    simulated-time half (delivery, backpressure, turnaround) lives in
    :func:`run_online_hedm`.
    """
    for w0 in range(0, frames.shape[0], window):
        chunk = reduce_frames(frames[w0:w0 + window], dark,
                              threshold=threshold, use_kernel=use_kernel)
        for r in chunk:
            r.frame_id += w0
        yield chunk


@dataclass
class OnlineHEDMResult:
    """Outcome of a streamed stage-1 run (times in simulated seconds)."""
    reduced: List[ReducedFrame]
    window_done: List[float]       # completion time of each reduce window
    turnaround: float              # last window done = end-to-end latency
    stream: "object"               # StreamReport of the ingest side


def run_online_hedm(fabric: Fabric, frames: np.ndarray, dark: np.ndarray,
                    rate_hz: Optional[float] = 10.0, window: int = 8,
                    threshold: float = 200.0, use_kernel: bool = True,
                    cache_frames: Optional[int] = None,
                    reduce_time_per_frame: Optional[float] = None
                    ) -> OnlineHEDMResult:
    """Online HEDM: ingest a streamed acquisition and reduce per window.

    Frames stream through a :class:`repro.core.streaming.StreamStager`
    (scatter + ring broadcast, sliding window of ``cache_frames`` frames —
    ``None`` keeps the whole scan resident); every full window is reduced
    FROM THE STAGED NODE-LOCAL REPLICA the moment its last frame lands,
    overlapping compute with acquisition. Consumed frames are released
    back to the window (enabling eviction/backpressure).

    ``reduce_time_per_frame`` is the simulated stage-1 cost per frame (s);
    ``None`` charges the measured wall time of the real reduction instead
    (the `ManyTaskEngine` payload idiom). Outputs are bit-identical to
    ``reduce_frames`` over the same stack.
    """
    from repro.core.api import StagingClient, StreamConfig
    from repro.core.streaming import DetectorSource

    if cache_frames is not None and cache_frames < window:
        raise ValueError(
            f"cache_frames ({cache_frames}) must be >= window ({window}): "
            f"frames are only released once a full reduce window has run, "
            f"so a smaller cache wedges the stream")
    # detector emits float32, same cast as the batch path's stream_to_fs —
    # keeps the 4-byte/pixel window accounting and replica decode honest
    frames = np.ascontiguousarray(frames, dtype=np.float32)
    F, H, W = frames.shape
    frame_bytes = H * W * 4
    config = StreamConfig(rate_hz=rate_hz,
                          window_bytes=(cache_frames or F) * frame_bytes)
    src = DetectorSource.from_frames(frames, rate_hz=config.rate_hz)
    stager = StagingClient(fabric).stream_stager(config)

    reduced: List[ReducedFrame] = []
    window_done: List[float] = []
    pending: List = []
    t_done = 0.0
    store = fabric.hosts[0].store
    for fid, path, buf, t_emit in src:
        pending.append(stager.ingest(path, buf, t_emit))
        if len(pending) == window or fid == F - 1:
            stack = np.stack([store.data[r.path].view(np.float32)
                              .reshape(H, W) for r in pending])
            t_wall = _time.perf_counter()
            chunk = reduce_frames(stack, dark, threshold=threshold,
                                  use_kernel=use_kernel)
            wall = _time.perf_counter() - t_wall
            dur = (reduce_time_per_frame * len(pending)
                   if reduce_time_per_frame is not None else wall)
            base = pending[0].frame_id
            for r in chunk:
                r.frame_id += base
            t_start = max(t_done, max(r.t_avail for r in pending))
            t_done = t_start + dur
            for r in pending:
                stager.release(r.path, t_done)
            reduced.extend(chunk)
            window_done.append(t_done)
            pending = []
    return OnlineHEDMResult(reduced=reduced, window_done=window_done,
                            turnaround=t_done, stream=stager.finish())


def run_batch_hedm(fabric: Fabric, frames: np.ndarray, dark: np.ndarray,
                   rate_hz: Optional[float] = 10.0, threshold: float = 200.0,
                   use_kernel: bool = True, mode: str = "collective",
                   reduce_time_per_frame: Optional[float] = None
                   ) -> Tuple[List[ReducedFrame], float, "object"]:
    """Stage-then-process baseline for the same scan as ``run_online_hedm``.

    The detector writes every frame to the shared FS first (acquisition
    completes at ``F / rate_hz`` simulated s; the producer write itself is
    not charged, which favors this baseline), the whole scan is staged with
    the batch engine `mode` through the unified client (concrete paths, no
    glob resolution or pinning — ``resolve=False``), then stage-1 runs
    over the staged node-local replicas in one pass. Returns
    ``(reduced, turnaround, StagingReport)``.
    """
    from repro.core.api import (BroadcastEntry, ENGINES, StagingClient,
                                StagingSpec)
    config = ENGINES.config_for(mode, batch_only=True)

    F, H, W = frames.shape
    paths = stream_to_fs(fabric, frames)
    t_acq = F / rate_hz if rate_hz else 0.0
    spec = StagingSpec([BroadcastEntry(files=tuple(paths), pin=False)])
    crep = StagingClient(fabric).stage(spec, config, t0=t_acq, resolve=False)
    # same arithmetic as the engine's returned completion time (bit-exact)
    rep = crep.reports[0]
    t_staged = t_acq + rep.total_time

    store = fabric.hosts[0].store
    stack = np.stack([store.data[p].view(np.float32).reshape(H, W)
                      for p in paths])
    t_wall = _time.perf_counter()
    reduced = reduce_frames(stack, dark, threshold=threshold,
                            use_kernel=use_kernel)
    wall = _time.perf_counter() - t_wall
    dur = (reduce_time_per_frame * F
           if reduce_time_per_frame is not None else wall)
    return reduced, t_staged + dur, rep


# ---------------------------------------------------------------------------
# interactive (multi-session) mode over the dataset catalog + service
# ---------------------------------------------------------------------------

def pack_reduced(reduced: Sequence[ReducedFrame]) -> np.ndarray:
    """Flat float32 write-back payload for a reduced scan: per frame a
    ``[frame_id, n_signal_pixels, n_spots]`` header followed by the
    ``(n_spots, 3)`` peak rows. Deterministic, so two sessions reducing
    the same staged dataset produce byte-identical buffers — the
    write-back byte-exactness criterion."""
    parts = []
    for r in reduced:
        parts.append(np.array([r.frame_id, r.n_signal_pixels, r.n_spots],
                              np.float32))
        parts.append(np.ascontiguousarray(r.peaks, np.float32).ravel())
    return (np.concatenate(parts) if parts else np.zeros(0, np.float32))


@dataclass
class SessionScript:
    """One tenant's plan: which datasets it reduces, in order, starting at
    ``t_start`` (simulated s). ``reduce_s_per_frame`` is the declared
    stage-1 cost (the ManyTaskEngine duration idiom — keeps multi-session
    schedules deterministic)."""
    name: str
    datasets: List[str]
    t_start: float = 0.0
    reduce_s_per_frame: float = 0.15


@dataclass
class InteractiveHEDMResult:
    """Outcome of a multi-session interactive run (times simulated s)."""
    outputs: Dict[str, Dict[str, np.ndarray]]   # session -> dataset -> packed
    result_paths: Dict[str, Dict[str, str]]     # session -> dataset -> FS path
    session_done: Dict[str, float]              # flush completion per session
    writeback: Dict[str, "object"]              # session -> StagingReport
    service: "object"                           # the StagingService (stats)
    turnaround: float                           # last session flush


def run_interactive_hedm(fabric: Fabric, scans: Dict[str, np.ndarray],
                         dark: np.ndarray,
                         sessions: Sequence[SessionScript],
                         budget_bytes: int, threshold: float = 200.0,
                         use_kernel: bool = False, mode: str = "collective",
                         collective_writeback: bool = True
                         ) -> InteractiveHEDMResult:
    """N concurrent analysis sessions over M scans through the staging
    service — the paper's interactive regime (§VI-B) plus write-back.

    Every scan lands on the shared FS (stage 0) and registers in the
    catalog. Sessions then interleave round-robin: each leases its next
    dataset (concurrent requests COALESCE into one collective stage;
    unleased residents evict under ``budget_bytes`` and re-stage
    transparently on a later miss), reduces stage-1 FROM THE RESIDENT
    NODE-LOCAL REPLICA (charged: replica read at ``local_read_bw`` +
    ``reduce_s_per_frame`` per frame), installs the packed result as a
    dirty replica, and releases the lease. When a session's script is
    done it FLUSHES its results to the shared FS (collective
    ``stage_out`` or the naive baseline).

    Outputs are bit-identical to reducing each scan directly — eviction
    and re-staging never change bytes, only times (tests assert this).
    """
    from contextlib import ExitStack

    from repro.core.api import ENGINES, ServiceConfig, StagingClient

    scans32 = {n: np.ascontiguousarray(f, dtype=np.float32)
               for n, f in scans.items()}
    for name, frames in scans32.items():
        stream_to_fs(fabric, frames, prefix=name)
    client = StagingClient(fabric, service=ServiceConfig(
        budget_bytes=budget_bytes,
        engine=ENGINES.config_for(mode, batch_only=True)))
    svc = client.service
    for name in scans32:
        svc.register(name, patterns=[f"{name}/frame_*.bin"])

    clocks = {s.name: s.t_start for s in sessions}
    outputs: Dict[str, Dict[str, np.ndarray]] = {s.name: {} for s in sessions}
    result_paths: Dict[str, Dict[str, str]] = {s.name: {} for s in sessions}
    c = fabric.constants

    session_done: Dict[str, float] = {}
    writeback: Dict[str, object] = {}
    with ExitStack() as stack:
        # session-scoped campaigns: any lease a tenant still holds when
        # the stack unwinds (including on error) is auto-released
        handles = {s.name: stack.enter_context(client.session(s.name))
                   for s in sessions}
        for step in range(max(len(s.datasets) for s in sessions)):
            for script in sessions:
                if step >= len(script.datasets):
                    continue
                ds = script.datasets[step]
                sess = handles[script.name]
                lease = sess.acquire(ds, clocks[script.name])
                entry = svc.catalog[ds]
                F, H, W = scans32[ds].shape
                store = fabric.hosts[0].store
                stack_ = np.stack([store.data[p].view(np.float32)
                                   .reshape(H, W) for p in entry.paths])
                reduced = reduce_frames(stack_, dark, threshold=threshold,
                                        use_kernel=use_kernel)
                packed = pack_reduced(reduced)
                t_compute = (lease.t_ready
                             + entry.nbytes / c.local_read_bw  # replica read
                             + script.reduce_s_per_frame * F)
                path, t_put = sess.put_result(ds, packed, t_compute)
                sess.release(ds, t_put)
                clocks[script.name] = t_put
                outputs[script.name][ds] = packed
                result_paths[script.name][ds] = path

        for script in sessions:
            rep, t_done = handles[script.name].flush(
                clocks[script.name], collective=collective_writeback)
            writeback[script.name] = rep
            session_done[script.name] = t_done
    return InteractiveHEDMResult(
        outputs=outputs, result_paths=result_paths,
        session_done=session_done, writeback=writeback, service=svc,
        turnaround=max(session_done.values()) if session_done else 0.0)


# ---------------------------------------------------------------------------
# stage 2: orientation fitting (batched Gauss-Newton)
# ---------------------------------------------------------------------------

N_GVEC = 24          # reference reciprocal-lattice directions per point


def _rotation(angles: jax.Array) -> jax.Array:
    """ZYZ Euler rotation matrix from 3 angles."""
    a, b, c = angles[0], angles[1], angles[2]
    ca, sa = jnp.cos(a), jnp.sin(a)
    cb, sb = jnp.cos(b), jnp.sin(b)
    cc, sc = jnp.cos(c), jnp.sin(c)
    rz1 = jnp.array([[ca, -sa, 0], [sa, ca, 0], [0, 0, 1.0]])
    ry = jnp.array([[cb, 0, sb], [0, 1.0, 0], [-sb, 0, cb]])
    rz2 = jnp.array([[cc, -sc, 0], [sc, cc, 0], [0, 0, 1.0]])
    return rz1 @ ry @ rz2


def make_gvectors(seed: int = 7) -> np.ndarray:
    rng = np.random.default_rng(seed)
    g = rng.normal(size=(N_GVEC, 3))
    return (g / np.linalg.norm(g, axis=1, keepdims=True)).astype(np.float32)


def forward_model(angles: jax.Array, gvec: jax.Array) -> jax.Array:
    """Simulated diffraction signature of an orientation (nonlinear)."""
    R = _rotation(angles)
    rotated = gvec @ R.T                              # (N,3)
    det_normal = jnp.array([0.0, 0.0, 1.0])
    proj = rotated @ det_normal                       # (N,)
    return jnp.concatenate([jnp.sin(3.0 * rotated[:, 0]) * proj,
                            jnp.cos(2.0 * rotated[:, 1]) * proj])


def fit_orientation(y_obs: jax.Array, gvec: jax.Array, theta0: jax.Array,
                    iters: int = 12, damping: float = 1e-3) -> jax.Array:
    """Gauss-Newton (Levenberg-damped) fit of one grid point."""
    def step(theta, _):
        r = forward_model(theta, gvec) - y_obs
        J = jax.jacfwd(lambda t: forward_model(t, gvec))(theta)   # (M,3)
        JtJ = J.T @ J + damping * jnp.eye(3)
        delta = jnp.linalg.solve(JtJ, J.T @ r)
        return theta - delta, jnp.sum(r * r)

    theta, losses = jax.lax.scan(step, theta0, None, length=iters)
    return theta


def fit_grid(y_obs: jax.Array, gvec: jax.Array, theta0: jax.Array,
             iters: int = 12) -> jax.Array:
    """vmapped FitOrientation over all grid points: (Npts, M) -> (Npts, 3).
    Under pjit the point axis shards over the full mesh — the many-task
    structure of Fig. 8 expressed as data parallelism."""
    return jax.vmap(lambda y, t0: fit_orientation(y, gvec, t0, iters))(
        y_obs, theta0)


def synth_grid_observations(n_points: int, gvec: np.ndarray, seed: int = 3,
                            noise: float = 0.01
                            ) -> Tuple[np.ndarray, np.ndarray]:
    """Ground-truth orientations + noisy observed signatures."""
    rng = np.random.default_rng(seed)
    truth = rng.uniform(-0.6, 0.6, (n_points, 3)).astype(np.float32)
    obs = jax.vmap(lambda t: forward_model(t, jnp.asarray(gvec)))(
        jnp.asarray(truth))
    obs = np.asarray(obs) + rng.normal(0, noise, obs.shape).astype(np.float32)
    return truth, obs
