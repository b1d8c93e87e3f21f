"""The HEDM stage-1 Pallas kernel against its jnp oracle (interpret mode on
the CPU): masks and counts bit for bit."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.hedm_reduce import _pick_tile, hedm_reduce
from repro.kernels.hedm_reduce_ref import reference


def _both(frames, dark, tile=None, **kw):
    """The kernel's and the oracle's (mask, counts) as numpy arrays."""
    f, d = jnp.asarray(frames), jnp.asarray(dark)
    got = hedm_reduce(f, d, threshold=150.0, tile_rows=tile, **kw)
    want = reference(f, d, threshold=150.0)
    return [tuple(np.asarray(a) for a in r) for r in (got, want)]


# (frames, rows, columns, forced row tile or None, dtype)
CONFIGS = [
    (4, 64, 64, None, "float32"),
    (2, 64, 64, 16, "float32"),
    (2, 72, 48, 32, "float32"),          # tile does not divide H
    (2, 40, 56, 8, "float32"),
    (2, 40, 56, None, "uint16"),
    (2, 40, 56, 16, "uint16"),
    (2, 24, 130, 8, "uint16"),           # W off both 8 and 128
    (1, 5, 7, None, "uint16"),           # smaller than one (8, 128) tile
    (1, 16, 2048, None, "uint16"),       # a strip at detector width
    (2, 33, 40, 16, "float32"),          # a partial last tile of one row
]


@pytest.mark.parametrize("F,H,W,tile,dtype", CONFIGS)
def test_hedm_reduce_matches_reference(F, H, W, tile, dtype):
    """Float32 frames and detector-dtype (uint16) frames and dark, tiled
    and untiled, give the oracle's masks and counts bit for bit, with a
    spot in the middle of the first frame and one crossing the corner of
    the last."""
    rng = np.random.default_rng(F * H * W)
    if dtype == "uint16":
        frames = rng.integers(0, 400, (F, H, W)).astype(np.uint16)
        dark = rng.integers(0, 20, (H, W)).astype(np.uint16)
    else:
        frames = rng.integers(0, 40, (F, H, W)).astype(np.float32)
        dark = np.full((H, W), 8.0, np.float32)
    frames[0, H // 2 - 1:H // 2 + 2, W // 2 - 1:W // 2 + 2] += 3000
    frames[-1, 0:3, 0:3] += 3000
    (m, c), (m_ref, c_ref) = _both(frames, dark, tile)
    assert np.array_equal(m, m_ref)
    assert np.array_equal(c, c_ref)
    assert c_ref[0] > 0                   # the spot was detected


# (rows, columns, forced row tile or None): divisible, H % tile != 0, and
# a partial last tile
BORDERS = [(24, 24, None), (24, 24, 8), (20, 16, 8), (21, 24, 16),
           (21, 24, 4)]


@pytest.mark.slow
@pytest.mark.parametrize("H,W,tile", BORDERS)
@pytest.mark.parametrize("seed", range(5))
def test_hedm_reduce_exact_on_noisy_borders(seed, H, W, tile):
    """High-amplitude noise makes frame-border pixels threshold-sensitive:
    the fused kernel must still match the oracle bit-for-bit there (the
    naive fusion of input-replicated halos does not)."""
    rng = np.random.default_rng(seed)
    frames = rng.integers(0, 400, (2, H, W)).astype(np.float32)
    dark = np.zeros((H, W), np.float32)
    (m, c), (m_ref, c_ref) = _both(frames, dark, tile)
    assert np.array_equal(m, m_ref)
    assert np.array_equal(c, c_ref)


@pytest.mark.parametrize("H,W,budget,tile", [
    (2048, 2048, 8 << 20, 64),            # the detector's frame
    (256, 256, 8 << 20, 256),             # fits: one tile
    (256, 256, 256 << 10, 16),
    (128, 64, 128 << 10, 32),
    (72, 48, 8 << 20, 72),                # one tile of H, not a power of 2
])
def test_pick_tile_fits_the_vmem_budget(H, W, budget, tile):
    assert _pick_tile(H, W, budget) == tile


def test_hedm_reduce_vmem_budget_forces_tiling():
    """A small VMEM budget must row-tile large frames without changing the
    result (the tile it picks, 32 of 128 rows, is a case above)."""
    rng = np.random.default_rng(4)
    frames = rng.integers(0, 40, (1, 128, 64)).astype(np.float32)
    frames[0, 60:64, 30:34] += 2500
    dark = np.full((128, 64), 8.0, np.float32)
    (m, c), (m_ref, c_ref) = _both(frames, dark, vmem_budget_bytes=128 << 10)
    assert np.array_equal(m, m_ref)
    assert np.array_equal(c, c_ref)


def test_hedm_reduce_auto_interpret_default():
    """interpret=None resolves by backend (interpreter off-TPU, compiled
    Mosaic on TPU) — the default path must run on whatever backend this is."""
    frames = jnp.zeros((1, 16, 16), jnp.float32)
    dark = jnp.zeros((16, 16), jnp.float32)
    mask, counts = hedm_reduce(frames, dark)          # must not raise
    assert int(np.asarray(counts)[0]) == 0


def test_hedm_reduce_finds_only_real_spots():
    rng = np.random.default_rng(1)
    frames = rng.integers(0, 30, (2, 96, 96)).astype(np.float32)
    dark = np.full((96, 96), 10.0, np.float32)
    _, counts = hedm_reduce(jnp.asarray(frames), jnp.asarray(dark),
                            threshold=500.0)
    assert int(np.asarray(counts).sum()) == 0   # pure noise -> no signal
