"""Work of the stage-1 filter, computed from shapes alone.

The least HBM traffic of one ``hedm_reduce`` call is what any
implementation has to move: every frame read once as stored, the dark
frame read once, one uint8 mask byte written per pixel and one int32
count per frame. Copies that an implementation makes on the way (padded
or gathered tiles) are not counted, so the share of the roofline reads
the same work whatever implements it.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
MASK_BYTES_PER_PIXEL = 1          # uint8 mask
COUNT_BYTES = 4                   # int32 signal-pixel count per frame


def hedm_reduce_min_bytes(frames: int, height: int, width: int,
                          frame_dtype: str, dark_dtype: str) -> int:
    """Least bytes one call over a ``(frames, height, width)`` stack moves
    between HBM and the chip."""
    px = height * width
    read = frames * px * np.dtype(frame_dtype).itemsize
    read += px * np.dtype(dark_dtype).itemsize
    write = frames * px * MASK_BYTES_PER_PIXEL + frames * COUNT_BYTES
    return int(read + write)


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
