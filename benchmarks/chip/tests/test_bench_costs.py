"""Least bytes of the stage-1 filter and the table of peaks."""
import pytest

from costs import hedm_reduce_min_bytes, peaks


def test_one_window_at_detector_size_by_hand():
    # 16 frames of 2048x2048 uint16 read (134,217,728 B), one uint16 dark
    # frame read (8,388,608 B), 16 uint8 masks written (67,108,864 B) and
    # 16 int32 counts written (64 B)
    assert hedm_reduce_min_bytes(16, 2048, 2048, "uint16", "uint16") == (
        134_217_728 + 8_388_608 + 67_108_864 + 64)


def test_float32_frames_and_odd_shape_by_hand():
    # 3 frames of 5x7 float32 (420 B), a float32 dark (140 B), 3 masks
    # (105 B), 3 counts (12 B)
    assert hedm_reduce_min_bytes(3, 5, 7, "float32", "float32") == 677


def test_peaks_of_v5e_and_unknown_kind():
    v5e = peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
