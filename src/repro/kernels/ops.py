"""Public jit'd wrapper for the Pallas stage-1 kernel.

The kernel auto-selects interpret mode off-TPU (on the CPU) and the
compiled Mosaic path on TPU; its jnp oracle is ``hedm_reduce_ref``.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import hedm_reduce as _hr


@functools.partial(jax.jit, static_argnames=("threshold",))
def hedm_reduce(frames, dark, threshold=100.0):
    # interpret auto-selection (compiled Mosaic on TPU, interpreter
    # elsewhere) lives in the kernel itself: interpret=None
    return _hr.hedm_reduce(frames, dark, threshold=threshold)
