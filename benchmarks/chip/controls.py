"""Controls: the reference put in the program's place in the next lower
precision, which the comparison that decides ``correct`` has to fail.

Stage 1: the reference filter in bfloat16 (the configuration states
float32), with every peak rounded to bfloat16. The control stands in for
the program's entry with the same signature, so a run drives it through
the cell's own window and check.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass

import ml_dtypes
import numpy as np

import jax.numpy as jnp

import ref_stage1


@dataclass
class Answer:
    frame_id: int
    n_signal_pixels: int
    n_spots: int
    peaks: np.ndarray


def scan_control(dtype=jnp.bfloat16):
    """A stand-in for ``reduce_frames_online`` computed by the reference in
    ``dtype``."""
    def reduce_frames_online(frames, dark, window=8, threshold=200.0,
                             use_kernel=True):
        d = jnp.asarray(dark)
        for w0 in range(0, len(frames), window):
            part = frames[w0:w0 + window]
            masks, counts = ref_stage1.filter_frames(
                jnp.asarray(part), d, threshold=threshold, dtype=dtype)
            masks, counts = np.asarray(masks), np.asarray(counts)
            out = []
            for j in range(len(part)):
                peaks = ref_stage1.peak_list(masks[j] > 0, part[j])
                peaks = peaks.astype(ml_dtypes.bfloat16).astype(np.float32)
                out.append(Answer(w0 + j, int(counts[j]), len(peaks), peaks))
            yield out
    return reduce_frames_online


@contextlib.contextmanager
def in_place_of(module, name, replacement):
    """Put ``replacement`` in place of ``module.name`` for the block."""
    original = getattr(module, name)
    setattr(module, name, replacement)
    try:
        yield
    finally:
        setattr(module, name, original)
