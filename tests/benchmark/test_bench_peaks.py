"""The table of peaks and the byte function of the roofline share."""
import pytest

from benchmark import peaks


def test_bytes_on_hand_worked_shapes():
    # one eval, one pick, 16,384 rows: six float32 columns read, three
    # entries written
    assert peaks.chain_kernel_bytes(1, 1, 16384) == 6 * 16384 * 4 + 12 == 393228
    # a full chunk of binpack-10k: 8 evals x 10 picks
    assert peaks.chain_kernel_bytes(8, 10, 16384) == 80 * 393228
    # spread-5k: 8,192 rows, 6 picks
    assert peaks.chain_kernel_bytes(4, 6, 8192) == 24 * (6 * 8192 * 4 + 12)
    # float64 columns: twice the bytes
    assert peaks.chain_kernel_bytes(4, 6, 8192, 8) == 24 * (6 * 8192 * 8 + 24)
    assert peaks.chain_kernel_bytes(0, 10, 16384) == 0
    with pytest.raises(ValueError):
        peaks.chain_kernel_bytes(-1, 1, 1)


def test_roofline_share_is_least_time_over_measured_time():
    moved = 819.0e9 * 0.001  # what the v5e moves in a millisecond
    assert peaks.roofline_pct(moved, 0.010, "TPU v5 lite") == pytest.approx(10.0)
    assert peaks.roofline_pct(moved, 0.0, "TPU v5 lite") is None


def test_an_unknown_device_is_an_error_not_a_default():
    with pytest.raises(KeyError):
        peaks.peak("cpu", "hbm_bytes_per_s")
    with pytest.raises(KeyError):
        peaks.roofline_pct(1.0, 1.0, "TPU v9")
    assert peaks.peak("TPU v5 lite", "bf16_flop_per_s") == 197.0e12
