"""Every program the benchmark drives or runs itself compiles at the cells'
sizes for a described TPU v5e (no chip needed). A compile that passes is
not a chip run."""
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import ref_stage1  # noqa: E402
import scangen  # noqa: E402
from harness import load_cell  # noqa: E402

SCAN = load_cell("nf_hedm_736.scan_w16")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_hedm_reduce_window_compiles(one_chip):
    from repro.kernels import hedm_reduce as hr
    cfg, w = SCAN.config, SCAN.traffic["window"]
    n = cfg["frame_size"]
    frames = _shape((w, n, n), jnp.uint16, one_chip)
    dark = _shape((n, n), jnp.uint16, one_chip)
    fn = jax.jit(lambda f, d: hr.hedm_reduce(f, d, threshold=cfg["threshold"],
                                             interpret=False))
    compiled = fn.lower(frames, dark).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_scan_render_and_reference_compile(one_chip):
    cfg = SCAN.config
    n = cfg["frame_size"]
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one_chip)
    scangen._render.lower(
        key, _shape((), jnp.uint32, one_chip), n=scangen.RENDER_CHUNK, size=n,
        spots=cfg["spots_per_frame"], sigma=tuple(cfg["spot_sigma_px"]),
        amplitude=tuple(cfg["spot_amplitude"]),
        margin=float(cfg["spot_margin_px"]),
        cdf=scangen.poisson_cdf(cfg["background_mean"])).compile()
    frames = _shape((ref_stage1.BATCH, n, n), jnp.uint16, one_chip)
    dark = _shape((n, n), jnp.uint16, one_chip)
    for dtype in (jnp.float32, jnp.bfloat16):
        ref_stage1.filter_frames.lower(
            frames, dark, threshold=cfg["threshold"], dtype=dtype).compile()

