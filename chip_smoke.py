"""Smoke run of the NF-HEDM main path on a TPU at detector size.

    python chip_smoke.py              # one chip: stage 1, staged path, stage 2
    python chip_smoke.py --chips 4    # four chips: staged replication only

One chip: the paper's NF-HEDM scan (736 frames of 2048x2048 uint16, about
6.2 GB) is reduced window by window through ``reduce_frames_online`` with the
compiled ``hedm_reduce`` kernel, and every window's masks and counts are
checked against ``hedm_reduce_ref.reference`` on the same chip. The first 16
frames then go through ``run_batch_hedm`` on a simulated ``Fabric`` (the
``StagingClient`` collective engine) and must give the same peak lists.
Stage 2 fits 4,109 grid points with ``fit_grid``.

Four chips: one 16-frame window of the scan is striped 1/4 per chip and
all-gathered (``staged_restore``), against four full host-to-device copies;
every chip's own shard must equal the host window byte for byte.

Each phase prints one line. The wall seconds printed are one run's, not a
benchmark. Any failed check raises and ends the run with a non-zero exit.
The last line of a passing run is one JSON object naming the device. The
script refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core.fabric import BGQ, Fabric  # noqa: E402
from repro.core.staging import staged_restore  # noqa: E402
from repro.hedm.pipeline import (fit_grid, make_gvectors,  # noqa: E402
                                 reduce_frames_online, run_batch_hedm,
                                 simulate_detector_frames,
                                 synth_grid_observations)
from repro.kernels import ops  # noqa: E402
from repro.kernels.hedm_reduce_ref import reference  # noqa: E402

# The paper's NF-HEDM scan (§VI-A): 736 frames from a 2048x2048 detector.
SCAN_FRAMES = 736
FRAME_SIZE = 2048
WINDOW = 16
UNIQUE_FRAMES = 32       # frames rendered from the seed; the scan repeats them
THRESHOLD = 200.0
STAGED_FRAMES = 16
FIT_POINTS = 4109        # the paper's FF-HEDM stage-2 job count
MIN_RECOVERED = 0.90
RECOVERED_RAD = 0.05
CACHE_DIR = ROOT / ".jax_cache"


class SmokeFailure(RuntimeError):
    """A phase's output disagreed with its reference."""


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    it. ``JAX_COMPILATION_CACHE_DIR``, when set, is that directory and JAX
    reads it itself; otherwise the cache sits at one fixed path inside the
    checkout, so that every run of this checkout finds it again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _wall(t0: float) -> str:
    return f"wall_s_one_run={time.perf_counter() - t0:.3f}"


def make_scan(n_frames: int, size: int, n_unique: int, seed: int):
    """A uint16 scan of ``n_frames`` frames that cycles through ``n_unique``
    frames rendered from ``seed``, and its uint16 dark frame."""
    frames, dark = simulate_detector_frames(n_unique, size=size, seed=seed)
    frames = np.clip(np.rint(frames), 0, 65535).astype(np.uint16)
    dark = np.clip(np.rint(dark), 0, 65535).astype(np.uint16)
    return frames[np.arange(n_frames) % n_unique], dark


def check_kernel_compiled(window: int, size: int) -> None:
    """The kernel as ``reduce_frames`` calls it lowers to a Mosaic custom
    call, not to the interpreter."""
    x = jax.ShapeDtypeStruct((window, size, size), jnp.uint16)
    d = jax.ShapeDtypeStruct((size, size), jnp.uint16)
    hlo = ops.hedm_reduce.lower(x, d, threshold=THRESHOLD).as_text()
    if "tpu_custom_call" not in hlo:
        raise SmokeFailure("hedm_reduce did not lower to tpu_custom_call: "
                           "the kernel would run in interpret mode")
    print(f"phase kernel: tpu_custom_call present in the lowered "
          f"hedm_reduce ({window}x{size}x{size} uint16)", flush=True)


def phase_stage1(scan: np.ndarray, dark: np.ndarray, window: int):
    """Stage 1 over the whole scan in windows. Returns the peak lists."""
    t0 = time.perf_counter()
    ref = jax.jit(reference, static_argnames=("threshold",))
    d = jnp.asarray(dark)
    reduced = []
    n_windows = 0
    for w0, chunk in zip(range(0, len(scan), window),
                         reduce_frames_online(scan, dark, window=window,
                                              threshold=THRESHOLD)):
        x = jnp.asarray(scan[w0:w0 + window])
        masks, counts = ops.hedm_reduce(x, d, threshold=THRESHOLD)
        masks_ref, counts_ref = ref(x, d, threshold=THRESHOLD)
        if not bool(jnp.array_equal(masks, masks_ref)):
            raise SmokeFailure(f"stage 1 window at frame {w0}: masks differ "
                               f"from the reference")
        if not bool(jnp.array_equal(counts, counts_ref)):
            raise SmokeFailure(f"stage 1 window at frame {w0}: counts differ "
                               f"from the reference")
        if [r.n_signal_pixels for r in chunk] != counts_ref.tolist():
            raise SmokeFailure(f"stage 1 window at frame {w0}: "
                               f"reduce_frames_online counts differ")
        reduced.extend(chunk)
        n_windows += 1
    if [r.frame_id for r in reduced] != list(range(len(scan))):
        raise SmokeFailure("stage 1 did not reduce every frame once")
    peak = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use", "not reported")
    F, H, W = scan.shape
    print(f"phase stage1: frames={F} frame={H}x{W} {scan.dtype} "
          f"windows={n_windows} masks_and_counts_equal_reference="
          f"{n_windows}/{n_windows} spots={sum(r.n_spots for r in reduced)} "
          f"signal_px={sum(r.n_signal_pixels for r in reduced)} "
          f"peak_bytes_in_use={peak} "
          f"{_wall(t0)}", flush=True)
    return reduced


def phase_staged(frames: np.ndarray, dark: np.ndarray, expected) -> None:
    """The same frames staged through the collective engine on a simulated
    fabric, then reduced: peak lists must equal ``expected``."""
    t0 = time.perf_counter()
    fabric = Fabric(n_hosts=4, constants=BGQ)
    reduced, _, rep = run_batch_hedm(fabric, frames, dark,
                                     threshold=THRESHOLD)
    for got, want in zip(reduced, expected, strict=True):
        if (got.n_spots != want.n_spots
                or got.n_signal_pixels != want.n_signal_pixels
                or not np.array_equal(got.peaks, want.peaks)):
            raise SmokeFailure(f"staged path frame {got.frame_id}: peak list "
                               f"differs from stage 1")
    print(f"phase staged: frames={len(frames)} engine=collective "
          f"hosts={len(fabric.hosts)} scan_bytes={rep.total_bytes} "
          f"fs_read_bytes={rep.fs_bytes} "
          f"peaks_equal_stage1={len(reduced)}/{len(expected)} {_wall(t0)}",
          flush=True)


def phase_stage2(n_points: int, min_share: float) -> float:
    """Stage-2 orientation fit; the share of points recovered within
    ``RECOVERED_RAD`` must reach ``min_share``."""
    t0 = time.perf_counter()
    gvec = make_gvectors()
    truth, obs = synth_grid_observations(n_points, gvec)
    fit = fit_grid(jnp.asarray(obs), jnp.asarray(gvec),
                   jnp.zeros((n_points, 3), jnp.float32))
    err = np.abs(np.asarray(fit) - truth).max(axis=1)
    share = float((err < RECOVERED_RAD).mean())
    print(f"phase stage2: points={n_points} recovered_within_"
          f"{RECOVERED_RAD}rad={share:.4f} (need >= {min_share}) "
          f"{_wall(t0)}", flush=True)
    if not share >= min_share:
        raise SmokeFailure(f"stage 2 recovered {share:.4f} < {min_share}")
    return share


def _check_replicas(arr: jax.Array, host: np.ndarray, devices, what: str):
    shards = arr.addressable_shards
    if sorted(s.device.id for s in shards) != sorted(d.id for d in devices):
        raise SmokeFailure(f"{what}: shards are not one per chip")
    want = host.view(np.uint8)
    for s in shards:
        got = np.asarray(s.data)
        if got.shape != host.shape or not np.array_equal(got.view(np.uint8),
                                                         want):
            raise SmokeFailure(f"{what}: chip {s.device.id} holds no "
                               f"byte-exact replica")


def phase_replicate(window: np.ndarray, devices) -> None:
    """Stripe ``window`` 1/P per chip and all-gather it, against P full
    host-to-device copies; every chip's shard must be a byte-exact replica."""
    n = len(devices)
    mesh = Mesh(np.array(devices), ("data",))
    q, rest = divmod(len(window), n)
    if rest:
        raise ValueError(f"window of {len(window)} frames does not split "
                         f"over {n} chips")
    stripes = {i: window[i * q:(i + 1) * q] for i in range(n)}

    staged_restore(mesh, stripes, "data").block_until_ready()      # compile
    t0 = time.perf_counter()
    staged = staged_restore(mesh, stripes, "data")
    staged.block_until_ready()
    t_staged = time.perf_counter() - t0

    t0 = time.perf_counter()
    naive = jax.device_put(window, NamedSharding(mesh, P()))
    naive.block_until_ready()
    t_naive = time.perf_counter() - t0

    _check_replicas(staged, window, devices, "staged all-gather")
    _check_replicas(naive, window, devices, "full copies")
    print(f"phase replicate: chips={n} window={window.shape} {window.dtype} "
          f"({window.nbytes} B) byte_exact_replicas={n}/{n} "
          f"host_to_device_bytes staged={window.nbytes} "
          f"naive={n * window.nbytes} wall_s_one_run staged={t_staged:.4f} "
          f"naive={t_naive:.4f}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    dev = device_info()
    print(f"phase device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        sys.exit(f"no TPU: JAX found {dev['platform']} devices")
    if dev["count"] < args.chips:
        sys.exit(f"--chips {args.chips} needs {args.chips} chips, "
                 f"JAX found {dev['count']}")
    print(f"phase cache: compile cache at {use_compile_cache()}", flush=True)

    if args.chips == 4:
        window, _ = make_scan(WINDOW, FRAME_SIZE, WINDOW, args.seed)
        phase_replicate(window, jax.devices()[:4])
    else:
        check_kernel_compiled(WINDOW, FRAME_SIZE)
        t1 = time.perf_counter()
        scan, dark = make_scan(SCAN_FRAMES, FRAME_SIZE, UNIQUE_FRAMES,
                               args.seed)
        print(f"phase scan: {SCAN_FRAMES} frames ({UNIQUE_FRAMES} unique) "
              f"from seed {args.seed}, {scan.nbytes} B {_wall(t1)}",
              flush=True)
        reduced = phase_stage1(scan, dark, WINDOW)
        phase_staged(scan[:STAGED_FRAMES], dark, reduced[:STAGED_FRAMES])
        phase_stage2(FIT_POINTS, MIN_RECOVERED)
    print(f"phase done: {_wall(t0)}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
