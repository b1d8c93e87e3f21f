"""HEDM application: stage-1 reduction and stage-2 orientation fitting."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.hedm.pipeline import (fit_grid, label_components, label_runs,
                                 make_gvectors, mask_rows, reduce_frames,
                                 run_centroids, simulate_detector_frames,
                                 stream_to_fs, synth_grid_observations,
                                 _union_find_label)
from repro.core.fabric import Fabric
from repro.kernels.hedm_reduce_ref import reference


def test_stage1_detects_spots():
    frames, dark = simulate_detector_frames(3, size=96, n_spots=4, seed=1)
    red = reduce_frames(frames, dark, threshold=200.0, use_kernel=True)
    assert all(r.n_spots >= 1 for r in red)
    for r in red:
        assert r.peaks.shape == (r.n_spots, 3)
        assert r.n_signal_pixels > 0


def test_stage1_reduction_is_sparse():
    """Paper: 8 MB frames reduce to ~1 MB of signal — mask must be sparse."""
    frames, dark = simulate_detector_frames(2, size=128, n_spots=6, seed=2)
    red = reduce_frames(frames, dark, threshold=200.0)
    for r in red:
        assert r.n_signal_pixels < 0.1 * 128 * 128


def test_union_find_labeling():
    mask = np.zeros((8, 8), bool)
    mask[1:3, 1:3] = True
    mask[5:7, 5:7] = True
    labels, n = _union_find_label(mask)
    assert n == 2
    assert labels[1, 1] != labels[5, 5]


def test_vectorized_labeler_matches_union_find():
    """The run-based two-pass labeler is a drop-in for the pixel-loop
    reference: identical labels AND identical numbering on random masks."""
    rng = np.random.default_rng(7)
    for _ in range(40):
        H = int(rng.integers(1, 48))
        W = int(rng.integers(1, 48))
        mask = rng.random((H, W)) < rng.uniform(0.05, 0.8)
        l_ref, n_ref = _union_find_label(mask)
        l_vec, n_vec = label_components(mask)
        assert n_ref == n_vec
        assert np.array_equal(l_ref, l_vec)


def test_labeler_edge_cases():
    empty = np.zeros((6, 6), bool)
    labels, n = label_components(empty)
    assert n == 0 and not labels.any()
    full = np.ones((5, 9), bool)
    labels, n = label_components(full)
    assert n == 1 and (labels == 1).all()
    one_px = np.zeros((1, 1), bool)
    one_px[0, 0] = True
    labels, n = label_components(one_px)
    assert n == 1 and labels[0, 0] == 1
    # snake: single 8-shaped component that forces cross-row merging
    snake = np.zeros((5, 5), bool)
    snake[0, :] = snake[2, :] = snake[4, :] = True
    snake[1, 0] = snake[3, 4] = True
    labels, n = label_components(snake)
    assert n == 1
    assert np.array_equal(*[x[0] for x in [label_components(snake),
                                           _union_find_label(snake)]])


def test_bincount_centroids_match_per_label_scan():
    """reduce_frames' one-pass weighted centroids equal the per-label
    nonzero-scan they replaced."""
    frames, dark = simulate_detector_frames(2, size=96, n_spots=5, seed=4)
    red = reduce_frames(frames, dark, threshold=200.0, use_kernel=False)
    from repro.kernels.hedm_reduce_ref import reference
    import jax.numpy as jnp
    masks, _ = reference(jnp.asarray(frames), jnp.asarray(dark),
                         threshold=200.0)
    for r, frame, mask in zip(red, frames, np.asarray(masks)):
        labels, n = label_components(mask > 0)
        assert n == r.n_spots
        for lbl in range(1, n + 1):
            ys, xs = np.nonzero(labels == lbl)
            inten = frame[ys, xs]
            w = inten / max(inten.sum(), 1e-9)
            np.testing.assert_allclose(
                r.peaks[lbl - 1],
                [(ys * w).sum(), (xs * w).sum(), inten.sum()], rtol=1e-4)


def _dense_peaks(labels, n, frame):
    """Centroids from a painted label image: a ``flatnonzero`` scan of the
    whole image, a ``divmod`` index grid and one bincount a moment. The
    reference the run-based centroids must equal bit for bit."""
    H, W = labels.shape
    yy, xx = np.divmod(np.arange(H * W), W)
    lab = labels.ravel()
    sel = np.flatnonzero(lab)
    l_s, v_s = lab[sel], frame.ravel()[sel].astype(np.float64)
    s_i = np.bincount(l_s, weights=v_s, minlength=n + 1)
    s_y = np.bincount(l_s, weights=v_s * yy[sel], minlength=n + 1)
    s_x = np.bincount(l_s, weights=v_s * xx[sel], minlength=n + 1)
    denom = np.maximum(s_i, 1e-9)
    return np.stack([s_y / denom, s_x / denom, s_i],
                    axis=1)[1:].astype(np.float32)


def _mask(case):
    if case == "empty":
        return np.zeros((6, 6), bool)
    if case == "full":
        return np.ones((5, 9), bool)
    if case == "edge_columns":              # runs touch column 0 and W-1
        m = np.zeros((7, 10), bool)
        m[:, 0] = m[:, -1] = m[3, :] = True
        m[5, 6:] = m[1, :2] = True
        return m
    if case == "corner":                    # one set pixel at (0, 0)
        m = np.zeros((4, 4), bool)
        m[0, 0] = True
        return m
    if case == "single_pixels":
        m = np.zeros((9, 11), bool)
        m[::2, ::2] = True
        return m
    if case == "snake":
        m = np.zeros((5, 5), bool)
        m[0, :] = m[2, :] = m[4, :] = True
        m[1, 0] = m[3, 4] = True
        return m
    # labels that hinge on which rows are adjacent in the frame
    m = np.zeros((12, 10), bool)
    if case == "split_by_an_empty_row":     # one column, broken at row 4
        m[2:4, 3] = m[5:8, 3] = True
        m[9, :4] = m[11, 2:6] = True
        return m
    if case == "first_and_last_row":
        m[0, 1:4] = m[1, 3] = m[-1, 5:] = m[-2, 9] = True
        return m
    if case == "full_row":
        m[6, :] = True
        m[5, 0] = m[7, 9] = m[9, 4] = True
        return m
    rng = np.random.default_rng(int(case.split("_")[1]))
    H, W = (int(v) for v in rng.integers(1, 48, 2))
    return rng.random((H, W)) < rng.uniform(0.05, 0.8)


MASKS = ["empty", "full", "edge_columns", "corner", "single_pixels",
         "snake", "split_by_an_empty_row", "first_and_last_row",
         "full_row"] + [f"random_{i}" for i in range(6)]


@pytest.mark.parametrize("case", MASKS)
def test_label_components_is_the_painting_of_label_runs(case):
    mask = _mask(case)
    runs = label_runs(mask)
    painted = np.zeros(mask.shape, np.int32)
    for r, c0, c1, lbl in zip(runs.row, runs.col_s, runs.col_e, runs.label):
        painted[r, c0:c1] = lbl
    labels, n = label_components(mask)
    assert n == runs.n
    assert labels.dtype == np.int32 and np.array_equal(labels, painted)
    assert np.array_equal(painted > 0, mask)
    key = runs.row * (mask.shape[1] + 1) + runs.col_s
    assert np.all(np.diff(key) > 0)         # row-major order


@pytest.mark.parametrize("dtype", [np.uint16, np.float32])
@pytest.mark.parametrize("case", MASKS)
def test_run_centroids_equal_the_dense_reference(case, dtype):
    mask = _mask(case)
    rng = np.random.default_rng(len(case))
    frame = (rng.integers(0, 2**16, mask.shape).astype(dtype)
             if dtype == np.uint16 else
             rng.uniform(0, 4000, mask.shape).astype(dtype))
    labels, n = _union_find_label(mask)
    got = run_centroids(label_runs(mask), frame)
    want = _dense_peaks(labels, n, frame)
    assert got.dtype == want.dtype == np.float32
    assert got.shape == want.shape == (n, 3)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("dtype", [np.uint16, np.float32])
def test_reduce_frames_peaks_equal_the_dense_reference(dtype):
    """Detector frames as the chip path takes them (uint16) and as the
    staged paths hand them over (float32)."""
    frames, dark = simulate_detector_frames(3, size=96, n_spots=6, seed=11)
    frames, dark = frames.astype(dtype), dark.astype(dtype)
    red = reduce_frames(frames, dark, threshold=200.0, use_kernel=False)
    masks, _ = reference(jnp.asarray(frames), jnp.asarray(dark),
                         threshold=200.0)
    assert sum(r.n_spots for r in red) > 0
    for r, frame, mask in zip(red, frames, np.asarray(masks)):
        labels, n = _union_find_label(mask > 0)
        assert r.n_spots == n
        assert np.array_equal(r.peaks, _dense_peaks(labels, n, frame))


@pytest.mark.parametrize("case", MASKS)
def test_label_runs_of_the_occupied_rows_equal_the_whole_frame(case):
    mask = _mask(case)
    rows = np.flatnonzero(mask.any(axis=1))
    whole, part = label_runs(mask), label_runs(mask[rows], rows)
    assert part.n == whole.n
    for got, want in zip(part[:4], whole[:4]):
        assert np.array_equal(got, want)
    if case == "split_by_an_empty_row":
        assert whole.n == 4               # the broken column is two spots


def _compact_reference(mask):
    """``mask_rows`` of one frame in numpy: ``any``, ``flatnonzero`` and
    ``packbits``."""
    H, W = mask.shape
    occupied = np.flatnonzero(mask.any(axis=1))
    keep = occupied[:H // 8]
    return keep, len(occupied), np.packbits(mask[keep], axis=1,
                                            bitorder="little")


def _frame_with_rows(n, H=64, W=64, seed=0):
    rng = np.random.default_rng(seed)
    m = np.zeros((H, W), np.uint8)
    for r in rng.choice(H, n, replace=False):
        m[r, rng.choice(W, rng.integers(1, W + 1), replace=False)] = 1
    return m


@pytest.mark.parametrize("occupied,W", [(0, 64), (3, 64), (8, 64), (9, 64),
                                        (40, 64), (64, 64), (5, 60)])
def test_mask_rows_equal_numpy_any_flatnonzero_and_packbits(occupied, W):
    """64 rows give a capacity of 8: a frame exactly at it, one row over
    it, a full frame, an empty one, and a width off the byte."""
    masks = np.stack([_frame_with_rows(occupied, W=W, seed=s)
                      for s in range(3)])
    rows, n_rows, packed = (np.asarray(a) for a in mask_rows(masks))
    assert rows.shape == (3, 8) and rows.dtype == np.int32
    assert packed.shape == (3, 8, -(-W // 8)) and packed.dtype == np.uint8
    for f, mask in enumerate(masks):
        keep, n, bits = _compact_reference(mask > 0)
        k = len(keep)
        assert n_rows[f] == n == occupied
        assert np.array_equal(rows[f, :k], keep)
        assert np.array_equal(packed[f, :k], bits)
        assert np.array_equal(np.unpackbits(packed[f, :k], axis=1, count=W,
                                            bitorder="little"), mask[keep])


@pytest.mark.parametrize("size,spots,seed,use_kernel", [
    (64, 1, 5, False), (64, 4, 5, False), (96, 6, 11, True),
    (128, 2, 3, False), (256, 6, 3, False)])
def test_reduce_frames_equals_the_dense_path(size, spots, seed, use_kernel):
    """Row-sparse copies back give exactly the peaks of labeling every
    frame's whole mask; the scans take the compact path, the overflow one,
    or both."""
    frames, dark = simulate_detector_frames(4, size=size, n_spots=spots,
                                            seed=seed)
    frames, dark = frames.astype(np.uint16), dark.astype(np.uint16)
    red = reduce_frames(frames, dark, threshold=200.0, use_kernel=use_kernel)
    masks, counts = reference(jnp.asarray(frames), jnp.asarray(dark),
                              threshold=200.0)
    for r, frame, mask, count in zip(red, frames, np.asarray(masks),
                                     np.asarray(counts)):
        runs = label_runs(mask > 0)
        assert (r.n_signal_pixels, r.n_spots) == (int(count), runs.n)
        assert np.array_equal(r.peaks, run_centroids(runs, frame))
    assert sum(r.n_spots for r in red) > 0


def test_detector_sim_spots_are_gaussian_and_bright():
    """Vectorized rendering still produces detectable bright spots well
    above the Poisson background."""
    frames, dark = simulate_detector_frames(3, size=64, n_spots=3, seed=9)
    assert frames.shape == (3, 64, 64) and frames.dtype == np.float32
    for f in frames:
        assert f.max() > 500            # amp >= 800 minus overlap losses
    no_spots, _ = simulate_detector_frames(2, size=64, n_spots=0, seed=9)
    assert no_spots.max() < 40          # pure Poisson(8) background


def test_stage2_recovers_orientations():
    gvec = make_gvectors()
    truth, obs = synth_grid_observations(128, gvec, noise=0.005)
    fit = fit_grid(jnp.asarray(obs), jnp.asarray(gvec),
                   jnp.zeros((128, 3)))
    err = np.abs(np.asarray(fit) - truth).max(axis=1)
    assert (err < 0.05).mean() > 0.7      # local minima are physical


def test_detector_stream_to_fs():
    fab = Fabric(n_hosts=2)
    frames, _ = simulate_detector_frames(3, size=32, n_spots=1)
    paths = stream_to_fs(fab, frames)
    assert len(paths) == 3
    assert fab.fs.size(paths[0]) == 32 * 32 * 4
