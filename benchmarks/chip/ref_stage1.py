"""Plain reference of NF-HEDM stage 1, independent of the code under test.

Filter: subtract the dark frame (clamped at 0), 3x3 median with edge
replication, 3x3 Laplacian of the median with edge replication, then a
pixel is signal where the Laplacian exceeds the threshold and the median
exceeds half of it. Written in ``jax.numpy`` with a sort for the median;
in float32 every value is an integer below 2**24, so the result is exact.
``dtype=jnp.bfloat16`` gives the lower-precision control.

Peaks: 4-connected components (``scipy.ndimage.label``), numbered by
their first pixel in row-major order, with intensity-weighted centroids
and summed intensity of the raw frame, accumulated in float64.
"""
from __future__ import annotations

import functools

import numpy as np
from scipy import ndimage

import jax
import jax.numpy as jnp

FOUR_CONNECTED = np.array([[0, 1, 0], [1, 1, 1], [0, 1, 0]], bool)
MIN_WEIGHT = 1e-9        # a component of zero weight has its centroid at 0
BATCH = 8                # frames filtered in one call


def _neighbours(img):
    H, W = img.shape
    p = jnp.pad(img, 1, mode="edge")
    return [p[i:i + H, j:j + W] for i in range(3) for j in range(3)]


@functools.partial(jax.jit, static_argnames=("threshold", "dtype"))
def filter_frames(frames, dark, threshold: float, dtype=jnp.float32):
    """``(F, H, W)`` frames and an ``(H, W)`` dark frame -> uint8 masks and
    int32 signal-pixel counts, one frame at a time on the device."""
    def one(img):
        x = jnp.maximum(img.astype(dtype) - dark.astype(dtype), 0)
        med = jnp.sort(jnp.stack(_neighbours(x)), axis=0)[4]
        n = _neighbours(med)
        lap = 8 * n[4] - (n[0] + n[1] + n[2] + n[3] + n[5] + n[6] + n[7]
                          + n[8])
        return ((lap > threshold) & (med > threshold / 2)).astype(jnp.uint8)

    masks = jax.lax.map(one, frames)
    return masks, jnp.sum(masks, axis=(1, 2), dtype=jnp.int32)


def filter_batches(scan, dark, ids, threshold: float):
    """Masks and counts of frames ``ids`` of ``scan``, ``BATCH`` at a time
    (the last batch padded, so one program serves all): yields the ids of
    each batch, its masks on the device and its counts on the host."""
    for b0 in range(0, len(ids), BATCH):
        part = list(ids[b0:b0 + BATCH])
        idx = part + [part[-1]] * (BATCH - len(part))
        masks, counts = filter_frames(jnp.asarray(scan[idx]), dark,
                                      threshold=threshold)
        yield part, masks, np.asarray(counts)


def peak_list(mask: np.ndarray, frame: np.ndarray) -> np.ndarray:
    """``(n_spots, 3)`` float64 rows of centroid y, centroid x and summed
    intensity, in the order of each component's first pixel."""
    labels, n = ndimage.label(mask, structure=FOUR_CONNECTED)
    if n == 0:
        return np.zeros((0, 3))
    lab = labels.ravel()
    pix = np.flatnonzero(lab)
    comp = lab[pix] - 1
    first = np.full(n, pix[-1] + 1)
    np.minimum.at(first, comp, pix)
    rank = np.empty(n, np.int64)
    rank[np.argsort(first)] = np.arange(n)
    comp = rank[comp]
    v = frame.ravel()[pix].astype(np.float64)
    y, x = np.divmod(pix, mask.shape[1])
    s = np.bincount(comp, v, n)
    w = np.maximum(s, MIN_WEIGHT)
    return np.stack([np.bincount(comp, v * y, n) / w,
                     np.bincount(comp, v * x, n) / w, s], axis=1)


def peak_gaps(got: np.ndarray, want: np.ndarray):
    """Widest centroid gap in pixels and widest relative intensity gap
    between two peak lists of equal length; a non-finite gap is ``inf``."""
    if len(want) == 0:
        return 0.0, 0.0
    got = np.asarray(got, np.float64)
    pos = np.max(np.abs(got[:, :2] - want[:, :2]))
    rel = np.max(np.abs(got[:, 2] - want[:, 2])
                 / np.maximum(np.abs(want[:, 2]), MIN_WEIGHT))
    return tuple(float(v) if np.isfinite(v) else np.inf for v in (pos, rel))
