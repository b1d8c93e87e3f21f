"""Thin spellings of JAX mesh and ``shard_map`` APIs used across the repo.

``shard_map`` passes only the keywords a caller sets, so call sites can
leave ``check_vma``/``axis_names`` at JAX's defaults; ``axis_names``
selects the manual axes.
"""
from __future__ import annotations

from typing import Iterable, Optional

import jax
from jax import shard_map as _shard_map


def make_auto_mesh(shape, axis_names):
    """``jax.make_mesh`` with every axis of Auto type."""
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: Optional[bool] = None,
              axis_names: Optional[Iterable[str]] = None):
    """``jax.shard_map``; ``axis_names`` selects the manual axes."""
    kwargs = {}
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      **kwargs)
