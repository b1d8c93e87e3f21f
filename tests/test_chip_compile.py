"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: the TPU compiler, installed with JAX, compiles for a chip that
is described and not attached, and refuses what the chip would refuse (a
block shape off the (8, 128) tiling, a primitive Mosaic cannot lower, too
much VMEM). The topology is described inside a fixture, never at import,
so that only the test worker given this file loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core.staging import device_replicate
from repro.hedm import pipeline
from repro.hedm.pipeline import N_GVEC, fit_grid
from repro.kernels.hedm_reduce import hedm_reduce

FRAME = 2048
FIT_POINTS = 4109
SCAN_FRAMES = 736           # the NF-HEDM scan, 184 frames a chip on four


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs under /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure: no compiler
            jax.config.update("jax_enable_compilation_cache", was_on)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_kernel(one_chip, shape, dtype):
    F, H, W = shape
    x = jax.ShapeDtypeStruct((F, H, W), dtype, sharding=one_chip)
    d = jax.ShapeDtypeStruct((H, W), dtype, sharding=one_chip)
    # interpret=False: off the chip the kernel would pick the interpreter
    fn = functools.partial(hedm_reduce, threshold=200.0, interpret=False)
    return jax.jit(fn).lower(x, d).compile()


@pytest.mark.parametrize("dtype", ["uint16", "float32"])
def test_hedm_reduce_compiles_for_v5e_at_detector_size(one_chip, dtype):
    compiled = _compile_kernel(one_chip, (8, FRAME, FRAME), jnp.dtype(dtype))
    assert "tpu_custom_call" in compiled.as_text()


def test_hedm_reduce_compiles_for_v5e_with_partial_last_tile(one_chip):
    """2000 rows over the default 64-row tile: the last tile is partial."""
    compiled = _compile_kernel(one_chip, (8, 2000, FRAME), jnp.uint16)
    assert "tpu_custom_call" in compiled.as_text()


def test_fit_grid_compiles_for_v5e_at_paper_job_count(one_chip):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in ((FIT_POINTS, 2 * N_GVEC), (N_GVEC, 3), (FIT_POINTS, 3))]
    compiled = jax.jit(fit_grid).lower(*args).compile()
    assert compiled.memory_analysis() is not None


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices), ("data",))


def test_scan_all_gather_compiles_for_v5e_2x2_without_a_second_replica(
        four_chips):
    """Staging the 6.17-GB scan: 1/4 in, the whole scan out on every chip,
    and temporaries far below a second replica."""
    shape = (SCAN_FRAMES, FRAME, FRAME)
    x = jax.ShapeDtypeStruct(shape, jnp.uint16,
                             sharding=NamedSharding(four_chips, P("data")))
    compiled = jax.jit(lambda a: device_replicate(four_chips, a)).lower(
        x).compile()
    mem = compiled.memory_analysis()
    replica = int(np.prod(shape)) * 2
    assert "all-gather" in compiled.as_text()
    assert mem.argument_size_in_bytes == replica // 4
    assert mem.output_size_in_bytes == replica
    assert mem.temp_size_in_bytes < replica // 4


@pytest.mark.parametrize("window", [16, 8])
def test_resident_step_compiles_for_v5e_2x2_at_detector_size(
        four_chips, monkeypatch, window):
    """One step of ``reduce_resident`` over the scan's replica: the 16-frame
    step and the ragged 8-frame tail of a 184-frame share."""
    # off the chip the kernel would pick the interpreter
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    whole = NamedSharding(four_chips, P())
    args = (jax.ShapeDtypeStruct((SCAN_FRAMES, FRAME, FRAME), jnp.uint16,
                                 sharding=whole),
            jax.ShapeDtypeStruct((FRAME, FRAME), jnp.uint16, sharding=whole),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=whole))
    step = pipeline._own_frames_filter(four_chips, "data", window, 200.0)
    compiled = step.lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # each chip compacts its own frames: nothing crosses the interconnect
    assert not any(op in text for op in ("all-gather", "all-to-all",
                                         "all-reduce", "collective-permute"))
    # each chip returns its own window's uint8 masks (and padded counts,
    # and the compact rows, under a quarter of a frame in all)
    out = compiled.memory_analysis().output_size_in_bytes
    assert out // (FRAME * FRAME) == window


@pytest.mark.parametrize("window", [16, 8])
def test_mask_rows_compiles_for_v5e_at_detector_size(one_chip, window):
    """The compaction of a window's masks: an eighth of the rows, 8 pixels
    a byte, with their indices (tiles pad the counts): about a 64th of the
    masks' bytes out."""
    x = jax.ShapeDtypeStruct((window, FRAME, FRAME), jnp.uint8,
                             sharding=one_chip)
    mem = pipeline.mask_rows.lower(x).compile().memory_analysis()
    dense = window * FRAME * FRAME
    assert dense // 64 < mem.output_size_in_bytes < dense // 62
    assert mem.temp_size_in_bytes <= dense // 8
