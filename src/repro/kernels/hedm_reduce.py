"""Pallas TPU kernel for NF-HEDM Stage-1 image reduction (paper §VI-A).

Per-tile pipeline (one detector row tile per program, tile resident in VMEM):
  1. dark-frame (median background) subtraction,
  2. 3x3 median filter (19-exchange min/max sorting network — pure VPU ops,
     no data-dependent control flow),
  3. 3x3 Laplacian (edge/diffraction-spot response),
  4. threshold -> binary spot mask + per-tile signal-pixel count.

The median and Laplacian stages are FUSED: the kernel receives its tile with
a 2-pixel halo (rows gathered by the wrapper, columns edge-padded with it),
computes the median on the 1-halo-extended domain from ONE set of 9 shifted
neighborhoods, and takes the Laplacian directly from static slices of that
extended median — no second round of shifted copies (the unfused version
materialised 18). At interior tile boundaries the halo medians come from
real neighbouring rows; at true frame borders the reference semantics
replicate the COMPUTED median (not the input), so the kernel rebuilds the
median halo ring there by edge-replication — making the fused result
bit-identical to the reference oracle on arbitrary data.

Grid: (F, T) — frames x row tiles. Small frames run as one tile; frames
whose working set exceeds the VMEM budget are row-tiled, each tile carrying
a 2-row halo from its neighbours (halo exchange done as a wrapper-side
gather; on real hardware this is an overlapping DMA). Connected-component
labeling stays on the host (repro.hedm.pipeline) — control-flow-heavy, a
poor fit for the MXU/VPU; the paper runs it on cluster CPUs too.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

HALO = 2                       # median (1) + Laplacian (1) support rows


def _median9(vals):
    """Median of 9 same-shape arrays via the classic 19-exchange network."""
    v = list(vals)

    def sort2(i, j):
        lo = jnp.minimum(v[i], v[j])
        hi = jnp.maximum(v[i], v[j])
        v[i], v[j] = lo, hi

    pairs = [(1, 2), (4, 5), (7, 8), (0, 1), (3, 4), (6, 7), (1, 2), (4, 5),
             (7, 8), (0, 3), (5, 8), (4, 7), (3, 6), (1, 4), (2, 5), (4, 7),
             (4, 2), (6, 4), (4, 2)]
    for i, j in pairs:
        sort2(i, j)
    return v[4]


def _window9(ext, h, w):
    """The 3x3 neighborhood of an (h+2, w+2)-padded tile as 9 static slices
    (lax.slice — no materialised shifted copies beyond what the VPU needs)."""
    return [ext[di:di + h, dj:dj + w] for di in range(3) for dj in range(3)]


def _to_f32(x):
    """Widen a tile to float32. Mosaic has no direct cast from 8/16-bit
    integers (the detector's uint16) to float32; going through int32 is
    exact for every such value."""
    if jnp.issubdtype(x.dtype, jnp.integer) and x.dtype.itemsize < 4:
        x = x.astype(jnp.int32)
    return x.astype(jnp.float32)


def _kernel(ext_ref, dark_ref, mask_ref, count_ref, *, threshold: float,
            tile: int, width: int, height: int):
    """Fused subtract -> median -> Laplacian -> threshold on one row tile.

    ext_ref:   (1, 1, tile+4, width+4) frame tile with 2-px halo all around.
    dark_ref:  (1, tile+4, width+4) matching dark-frame tile.
    count_ref: (1, 1, 8, 128) signal-pixel count of the tile, broadcast over
               one whole (8, 128) vreg tile so the block is lane-dense.
    """
    img = _to_f32(ext_ref[0, 0])
    dark = _to_f32(dark_ref[0])
    img = jnp.maximum(img - dark, 0.0)                  # background subtract
    # median on the 1-halo-extended domain: rows/cols [-1, tile+1) x
    # [-1, width+1), from ONE set of 9 shifted neighborhoods
    med_ext = _median9(_window9(img, tile + 2, width + 2))
    # At a TRUE frame border the reference replicates the computed median,
    # not the input: a halo median there would see the border row three
    # times (2-px input replication) and differ. Rebuild those medians by
    # replication — the top halo only when this tile is the frame top
    # (interior halos hold real neighbour data), columns always, and every
    # row below global row height-1 (the bottom halo of the last tile AND
    # any padded tail rows when tile does not divide height) clamps to the
    # boundary row's median.
    t = pl.program_id(1)
    top = jnp.where(t == 0, med_ext[1:2], med_ext[0:1])
    med_ext = jnp.concatenate([top, med_ext[1:]], axis=0)
    r_star = height - t * tile        # local med_ext index of frame row H-1
    ridx = jax.lax.broadcasted_iota(jnp.int32, (tile + 2, 1), 0)
    # row r_star picked by a masked max over the row iota (Mosaic has no
    # dynamic sublane slice); exact, since every other row contributes -inf
    brow = jnp.max(jnp.where(ridx == jnp.clip(r_star, 0, tile + 1), med_ext,
                             -jnp.inf), axis=0, keepdims=True)
    med_ext = jnp.where(ridx > r_star, brow, med_ext)
    med_ext = jnp.concatenate([med_ext[:, 1:2], med_ext[:, 1:-1],
                               med_ext[:, -2:-1]], axis=1)
    # Laplacian straight from slices of the extended median — the fusion:
    # no second neighborhood build
    n = _window9(med_ext, tile, width)
    lap = 8.0 * n[4] - (n[0] + n[1] + n[2] + n[3] + n[5] + n[6] + n[7] + n[8])
    mask = (lap > threshold) & (n[4] > threshold * 0.5)
    mask_ref[0] = mask.astype(jnp.uint8)
    count_ref[0, 0] = jnp.full((8, 128), jnp.sum(mask.astype(jnp.int32)),
                               jnp.int32)


def _pick_tile(H: int, W: int, vmem_budget_bytes: int) -> int:
    """Largest power-of-two row tile whose f32 working set (ext tile, 9
    shifted median inputs, extended median, mask — ~12 live (tile+4, W+4)
    buffers) fits the VMEM budget. Interpret mode has no hard limit; the
    budget models the TPU."""
    tile = 1 << max(0, (H - 1).bit_length())         # next pow2 >= H
    while tile > 8 and 12 * (tile + 4) * (W + 4) * 4 > vmem_budget_bytes:
        tile //= 2
    return min(tile, H)


def hedm_reduce(frames: jax.Array, dark: jax.Array, threshold: float = 100.0,
                interpret: Optional[bool] = None,
                tile_rows: Optional[int] = None,
                vmem_budget_bytes: int = 8 << 20):
    """frames: (F,H,W) uint16/f32 detector stack; dark: (H,W) background.
    Returns (mask (F,H,W) uint8, counts (F,) int32).

    interpret=None auto-selects: compiled Mosaic on a real TPU backend,
    interpreter elsewhere (Pallas does not lower on CPU). Frames whose
    working set exceeds ``vmem_budget_bytes`` are row-tiled (grid (F, T))
    with a 2-row halo; ``tile_rows`` forces a tile height for testing.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    F, H, W = frames.shape
    tile = tile_rows if tile_rows is not None else _pick_tile(
        H, W, vmem_budget_bytes)
    tile = max(1, min(tile, H))
    T = (H + tile - 1) // tile
    Hp = T * tile                                  # padded row count

    # halo exchange, wrapper-side: gather each tile's rows plus a 2-row /
    # 2-col edge-replicated halo into (F, T, tile+4, W+4) so the kernel is
    # pure slices + arithmetic (Mosaic-friendly; overlapping DMA on TPU).
    padded = jnp.pad(frames, ((0, 0), (HALO, HALO + Hp - H), (HALO, HALO)),
                     mode="edge")
    rows = (np.arange(T)[:, None] * tile
            + np.arange(tile + 2 * HALO)[None, :])          # (T, tile+4)
    ext = padded[:, rows, :]                                # (F,T,tile+4,W+4)
    dark_ext = jnp.pad(dark, ((HALO, HALO + Hp - H), (HALO, HALO)),
                       mode="edge")[rows, :]                # (T,tile+4,W+4)

    mask, counts = pl.pallas_call(
        functools.partial(_kernel, threshold=threshold, tile=tile, width=W,
                          height=H),
        out_shape=(jax.ShapeDtypeStruct((F, Hp, W), jnp.uint8),
                   jax.ShapeDtypeStruct((F, T, 8, 128), jnp.int32)),
        grid=(F, T),
        in_specs=[
            pl.BlockSpec((1, 1, tile + 2 * HALO, W + 2 * HALO),
                         lambda f, t: (f, t, 0, 0)),
            pl.BlockSpec((1, tile + 2 * HALO, W + 2 * HALO),
                         lambda f, t: (t, 0, 0)),
        ],
        out_specs=(pl.BlockSpec((1, tile, W), lambda f, t: (f, t, 0)),
                   pl.BlockSpec((1, 1, 8, 128), lambda f, t: (f, t, 0, 0))),
        interpret=interpret,
    )(ext, dark_ext)

    if Hp != H:     # padded tail rows carry replicated data: drop & recount
        mask = mask[:, :H]
        counts = jnp.sum(mask.astype(jnp.int32), axis=(1, 2))
    else:
        counts = jnp.sum(counts[:, :, 0, 0], axis=1)
    return mask, counts
