"""The benchmark's own detector scan, rendered on the device from a seed.

The distribution is that of the repository's ``simulate_detector_frames``:
Poisson background, isotropic Gaussian spots at uniform positions,
amplitudes and widths, and a Poisson dark frame, rounded to the detector's
uint16. Each frame is drawn from a key folded from the seed and its frame
index, so the scan is the same whatever chunk size renders it, and every
frame differs. The Poisson draw is an inverse-CDF lookup, exact up to the
float32 resolution of the uniform it inverts (the tail beyond a CDF of
1 - 2**-24 is cut).
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

STREAM_SCAN = 1
STREAM_DARK = 2
RENDER_CHUNK = 16       # frames rendered in one call


def seed_key(seed: int, stream: int) -> jax.Array:
    """A threefry key from the full width of ``seed`` (JAX's own
    ``random.key`` keeps only its low 32 bits)."""
    words = np.random.SeedSequence([seed % 2**64, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def poisson_cdf(mean: float) -> tuple:
    """CDF of Poisson(mean) at 0, 1, ... while it stays below 1 in float32."""
    cdf, p, k = [], math.exp(-mean), 0
    total = p
    while np.float32(total) < np.float32(1.0):
        cdf.append(float(np.float32(total)))
        k += 1
        p *= mean / k
        total += p
    return tuple(cdf)


def _poisson(key, shape, cdf):
    u = jax.random.uniform(key, shape, jnp.float32)
    out = jnp.zeros(shape, jnp.int32)
    for c in cdf:
        out = out + (u >= c).astype(jnp.int32)
    return out


def _frame(key, *, size, spots, sigma, amplitude, margin, cdf):
    k_bg, k_y, k_x, k_a, k_s = jax.random.split(key, 5)
    bg = _poisson(k_bg, (size, size), cdf).astype(jnp.float32)
    lo, hi = margin, size - margin
    cy = jax.random.uniform(k_y, (spots, 1), jnp.float32, lo, hi)
    cx = jax.random.uniform(k_x, (spots, 1), jnp.float32, lo, hi)
    amp = jax.random.uniform(k_a, (spots, 1), jnp.float32, *amplitude)
    sig = jax.random.uniform(k_s, (spots, 1), jnp.float32, *sigma)
    r = jnp.arange(size, dtype=jnp.float32)[None, :]
    gy = amp * jnp.exp(-((r - cy) ** 2) / (2 * sig ** 2))          # (S, H)
    gx = jnp.exp(-((r - cx) ** 2) / (2 * sig ** 2))                # (S, W)
    img = jnp.matmul(gy.T, gx, precision=jax.lax.Precision.HIGHEST)
    return jnp.clip(jnp.rint(bg + img), 0, 65535).astype(jnp.uint16)


@functools.partial(jax.jit, static_argnames=("n", "size", "spots", "sigma",
                                             "amplitude", "margin", "cdf"))
def _render(key, first, *, n, size, spots, sigma, amplitude, margin, cdf):
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        first + jnp.arange(n, dtype=jnp.uint32))
    return jax.vmap(functools.partial(
        _frame, size=size, spots=spots, sigma=sigma, amplitude=amplitude,
        margin=margin, cdf=cdf))(keys)


@functools.partial(jax.jit, static_argnames=("size", "cdf"))
def _dark(key, *, size, cdf):
    return _poisson(key, (size, size), cdf).astype(jnp.uint16)


def render_scan(config: dict, seed: int, chunk: int = RENDER_CHUNK):
    """The scan of ``config`` as the host holds it: ``(frames, size, size)``
    uint16 and its uint16 dark frame. Chunks are rendered on the device and
    copied back while the next one renders."""
    if config["dtype"] != "uint16":
        raise ValueError(f"scan dtype {config['dtype']!r}: only uint16 is "
                         f"rendered")
    n_frames, size = config["frames"], config["frame_size"]
    static = dict(size=size, spots=config["spots_per_frame"],
                  sigma=tuple(config["spot_sigma_px"]),
                  amplitude=tuple(config["spot_amplitude"]),
                  margin=float(config["spot_margin_px"]),
                  cdf=poisson_cdf(config["background_mean"]))
    key = seed_key(seed, STREAM_SCAN)
    scan = np.empty((n_frames, size, size), np.uint16)
    pending = None
    for f0 in range(0, n_frames, chunk):
        n = min(chunk, n_frames - f0)
        out = _render(key, jnp.uint32(f0), n=n, **static)
        out.copy_to_host_async()
        if pending is not None:
            scan[pending[0]:pending[0] + len(pending[1])] = np.asarray(
                pending[1])
        pending = (f0, out)
    if pending is not None:
        scan[pending[0]:] = np.asarray(pending[1])
    dark = np.asarray(_dark(seed_key(seed, STREAM_DARK), size=size,
                            cdf=poisson_cdf(config["dark_mean"])))
    return scan, dark
