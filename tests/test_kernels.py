"""Per-kernel allclose sweeps vs pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

key = jax.random.PRNGKey(0)


def rand(k, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.fold_in(key, k), shape, dtype)


# --------------------------- flash attention ------------------------------

SHAPES = [
    (2, 256, 8, 4, 64, True, 0),
    (1, 256, 4, 4, 128, True, 64),
    (2, 128, 8, 2, 32, False, 0),
    (1, 512, 8, 8, 64, True, 0),
    (1, 256, 16, 4, 64, True, 128),
]


@pytest.mark.parametrize("B,S,H,KV,hd,causal,win", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_reference(B, S, H, KV, hd, causal, win,
                                           dtype):
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.flash_attention_ref import reference
    q = rand(1, (B, S, H, hd), dtype)
    k = rand(2, (B, S, KV, hd), dtype)
    v = rand(3, (B, S, KV, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=win)
    ref = reference(q, k, v, causal=causal, window=win)
    tol = 2e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@given(bq=st.sampled_from([32, 64, 128]), bk=st.sampled_from([32, 64, 128]))
@settings(max_examples=9, deadline=None)
def test_flash_attention_block_shape_invariance(bq, bk):
    """Output must not depend on the VMEM tiling."""
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.flash_attention_ref import reference
    q, k, v = (rand(i, (1, 256, 4, 2, 64))[..., 0, :, :].transpose(0, 2, 1, 3)
               if False else rand(i, (1, 256, 4, 64)) for i in (4, 5, 6))
    kk = rand(7, (1, 256, 2, 64))
    vv = rand(8, (1, 256, 2, 64))
    out = flash_attention(q, kk, vv, causal=True, block_q=bq, block_k=bk)
    ref = reference(q, kk, vv, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


# --------------------------- mamba2 ssd -----------------------------------

@pytest.mark.parametrize("B,L,H,P,G,N,chunk", [
    (2, 128, 4, 16, 2, 8, 32),
    (1, 64, 2, 32, 1, 16, 16),
    (1, 256, 8, 16, 8, 8, 64),
])
def test_mamba2_scan_matches_reference(B, L, H, P, G, N, chunk):
    from repro.kernels.mamba2_scan import mamba2_scan
    from repro.kernels.mamba2_scan_ref import reference
    x = rand(10, (B, L, H, P))
    dt = jax.nn.softplus(rand(11, (B, L, H)))
    A = -jnp.exp(rand(12, (H,)))
    Bm = rand(13, (B, L, G, N))
    Cm = rand(14, (B, L, G, N))
    y, h = mamba2_scan(x, dt, A, Bm, Cm, chunk=chunk)
    y_ref, h_ref = reference(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=2e-4)


# --------------------------- rwkv6 wkv ------------------------------------

@pytest.mark.parametrize("B,L,H,N,chunk", [
    (2, 96, 3, 8, 32),
    (1, 64, 2, 16, 16),
    (1, 128, 4, 32, 32),
])
def test_rwkv6_wkv_matches_reference(B, L, H, N, chunk):
    from repro.kernels.rwkv6_wkv import rwkv6_wkv
    from repro.kernels.rwkv6_wkv_ref import reference
    r = rand(20, (B, L, H, N))
    k = rand(21, (B, L, H, N))
    v = rand(22, (B, L, H, N))
    w = jax.nn.sigmoid(rand(23, (B, L, H, N))) * 0.5 + 0.45
    u = rand(24, (H, N))
    o, s = rwkv6_wkv(r, k, v, w, u, chunk=chunk)
    o_ref, s_ref = reference(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=2e-4)


# --------------------------- hedm reduce ----------------------------------

def test_hedm_reduce_matches_reference():
    from repro.kernels.hedm_reduce import hedm_reduce
    from repro.kernels.hedm_reduce_ref import reference
    rng = np.random.default_rng(0)
    frames = rng.integers(0, 40, (4, 64, 64)).astype(np.float32)
    frames[1, 10:13, 40:43] += 3000
    dark = np.full((64, 64), 8.0, np.float32)
    m1, c1 = hedm_reduce(jnp.asarray(frames), jnp.asarray(dark), threshold=150.0)
    m2, c2 = reference(jnp.asarray(frames), jnp.asarray(dark), threshold=150.0)
    assert np.array_equal(np.asarray(m1), np.asarray(m2))
    assert np.array_equal(np.asarray(c1), np.asarray(c2))
    assert int(np.asarray(c1)[1]) > 0          # the spot was detected


def test_hedm_reduce_row_tiled_matches_untiled():
    """Row tiling with 2-row halo must be invisible: tiled == untiled ==
    reference, including when H is not a multiple of the tile."""
    from repro.kernels.hedm_reduce import hedm_reduce
    from repro.kernels.hedm_reduce_ref import reference
    rng = np.random.default_rng(3)
    for H, W, tile in [(64, 64, 16), (72, 48, 32), (40, 56, 8)]:
        frames = rng.integers(0, 40, (2, H, W)).astype(np.float32)
        frames[0, H // 2:H // 2 + 3, W // 2:W // 2 + 3] += 3000
        frames[1, 0:3, 0:3] += 3000            # spot crossing the edge
        dark = np.full((H, W), 8.0, np.float32)
        m_ref, c_ref = reference(jnp.asarray(frames), jnp.asarray(dark),
                                 threshold=150.0)
        m_t, c_t = hedm_reduce(jnp.asarray(frames), jnp.asarray(dark),
                               threshold=150.0, tile_rows=tile)
        assert np.array_equal(np.asarray(m_t), np.asarray(m_ref)), (H, W, tile)
        assert np.array_equal(np.asarray(c_t), np.asarray(c_ref)), (H, W, tile)


def test_hedm_reduce_uint16_frames_match_reference():
    """Detector-dtype (uint16) frames and dark, tiled and untiled, give the
    reference's masks and counts bit for bit."""
    from repro.kernels.hedm_reduce import hedm_reduce
    from repro.kernels.hedm_reduce_ref import reference
    rng = np.random.default_rng(5)
    frames = rng.integers(0, 400, (2, 40, 56)).astype(np.uint16)
    frames[0, 20:23, 30:33] += 3000
    dark = rng.integers(0, 20, (40, 56)).astype(np.uint16)
    m_ref, c_ref = reference(jnp.asarray(frames), jnp.asarray(dark),
                             threshold=150.0)
    for tile in (None, 16):
        m, c = hedm_reduce(jnp.asarray(frames), jnp.asarray(dark),
                           threshold=150.0, tile_rows=tile)
        assert np.array_equal(np.asarray(m), np.asarray(m_ref)), tile
        assert np.array_equal(np.asarray(c), np.asarray(c_ref)), tile
    assert int(np.asarray(c_ref)[0]) > 0


@pytest.mark.slow
def test_hedm_reduce_exact_on_noisy_borders():
    """High-amplitude noise makes frame-border pixels threshold-sensitive:
    the fused kernel must still match the oracle bit-for-bit there (the
    naive fusion of input-replicated halos does not)."""
    from repro.kernels.hedm_reduce import hedm_reduce
    from repro.kernels.hedm_reduce_ref import reference
    for seed in range(5):
        for H, W, tiles in [(24, 24, (None, 8)),     # divisible
                            (20, 16, (8,)),          # H % tile != 0
                            (21, 24, (16, 4))]:      # partial last tile
            rng = np.random.default_rng(seed)
            frames = rng.integers(0, 400, (2, H, W)).astype(np.float32)
            dark = np.zeros((H, W), np.float32)
            m_ref, c_ref = reference(jnp.asarray(frames), jnp.asarray(dark),
                                     threshold=150.0)
            for tile in tiles:
                m, c = hedm_reduce(jnp.asarray(frames), jnp.asarray(dark),
                                   threshold=150.0, tile_rows=tile)
                assert np.array_equal(np.asarray(m), np.asarray(m_ref)), \
                    (seed, H, W, tile)
                assert np.array_equal(np.asarray(c), np.asarray(c_ref)), \
                    (seed, H, W, tile)


def test_hedm_reduce_vmem_budget_forces_tiling():
    """A small VMEM budget must row-tile large frames without changing the
    result (and the picked tile must actually be smaller than the frame)."""
    from repro.kernels.hedm_reduce import _pick_tile, hedm_reduce
    from repro.kernels.hedm_reduce_ref import reference
    assert _pick_tile(256, 256, 8 << 20) >= 256       # fits: one tile
    small = _pick_tile(256, 256, 1 << 18)             # 256 KB budget: tiles
    assert small < 256
    rng = np.random.default_rng(4)
    frames = rng.integers(0, 40, (1, 128, 64)).astype(np.float32)
    frames[0, 60:64, 30:34] += 2500
    dark = np.full((128, 64), 8.0, np.float32)
    m_ref, c_ref = reference(jnp.asarray(frames), jnp.asarray(dark),
                             threshold=150.0)
    m, c = hedm_reduce(jnp.asarray(frames), jnp.asarray(dark),
                       threshold=150.0, vmem_budget_bytes=1 << 17)
    assert np.array_equal(np.asarray(m), np.asarray(m_ref))
    assert np.array_equal(np.asarray(c), np.asarray(c_ref))


def test_hedm_reduce_auto_interpret_default():
    """interpret=None resolves by backend (interpreter off-TPU, compiled
    Mosaic on TPU) — the default path must run on whatever backend this is."""
    from repro.kernels.hedm_reduce import hedm_reduce
    frames = jnp.zeros((1, 16, 16), jnp.float32)
    dark = jnp.zeros((16, 16), jnp.float32)
    mask, counts = hedm_reduce(frames, dark)          # must not raise
    assert int(np.asarray(counts)[0]) == 0


def test_hedm_reduce_finds_only_real_spots():
    from repro.kernels.hedm_reduce import hedm_reduce
    rng = np.random.default_rng(1)
    frames = rng.integers(0, 30, (2, 96, 96)).astype(np.float32)
    dark = np.full((96, 96), 10.0, np.float32)
    _, counts = hedm_reduce(jnp.asarray(frames), jnp.asarray(dark),
                            threshold=500.0)
    assert int(np.asarray(counts).sum()) == 0   # pure noise -> no signal


# --------------------- model-level chunked vs naive -----------------------

def test_ssd_chunked_equals_naive_model_path():
    from repro.models.mamba2 import ssd_chunked, ssd_naive
    x = rand(30, (2, 64, 2, 4, 8))
    dt = jax.nn.softplus(rand(31, (2, 64, 2, 4)))
    A = -jnp.exp(rand(32, (2, 4)))
    Bm = rand(33, (2, 64, 2, 16))
    Cm = rand(34, (2, 64, 2, 16))
    y1, h1 = ssd_naive(x, dt, A, Bm, Cm)
    y2, h2 = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-3)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-3)


def test_wkv_chunked_equals_naive_model_path():
    from repro.models.rwkv6 import wkv_chunked, wkv_naive
    r = rand(40, (2, 64, 3, 8))
    k = rand(41, (2, 64, 3, 8))
    v = rand(42, (2, 64, 3, 8))
    w = jax.nn.sigmoid(rand(43, (2, 64, 3, 8))) * 0.5 + 0.45
    u = rand(44, (3, 8))
    o1, s1 = wkv_naive(r, k, v, w, u)
    o2, s2 = wkv_chunked(r, k, v, w, u, chunk=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)


def test_blocked_attention_equals_dense():
    from repro.models.attention import (attention_bias, blocked_grouped_sdpa,
                                        grouped_sdpa)
    q = rand(50, (2, 256, 8, 32))
    k = rand(51, (2, 256, 4, 32))
    v = rand(52, (2, 256, 4, 32))
    for causal, win in [(True, 0), (True, 64), (False, 0)]:
        ref = grouped_sdpa(q, k, v,
                           attention_bias(256, 256, causal=causal, window=win),
                           32 ** -0.5)
        blk = blocked_grouped_sdpa(q, k, v, causal=causal, window=win,
                                   scale=32 ** -0.5, q_chunk=64)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(blk), atol=2e-5)
