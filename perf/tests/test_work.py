import pytest

from perf import work


def test_state_bytes_at_small_shapes():
    # w, r, p read and written once: 6 passes over (M+1)(N+1) fp32 values.
    assert work.cg_state_bytes_per_iteration(2, 2) == 6 * 3 * 3 * 4
    assert work.cg_state_bytes_per_iteration(40, 40) == 6 * 41 * 41 * 4
    assert work.cg_state_bytes_per_iteration(3, 5, itemsize=2) == 6 * 4 * 6 * 2


def test_state_bytes_at_the_largest_grid():
    per_iteration = work.cg_state_bytes_per_iteration(2400, 3200)
    assert per_iteration == 184_454_424
    least = work.least_seconds(per_iteration * 2449, 819e9)
    assert 0.55 < least < 0.552


def test_no_grid_below_two():
    with pytest.raises(ValueError):
        work.cg_state_bytes_per_iteration(1, 40)


def test_v5e_peak_is_published():
    peak = work.peak("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    assert peak["bf16_flops_per_s"] == 197e12
    assert "Google Cloud" in peak["source"]


def test_unknown_device_is_an_error():
    with pytest.raises(work.UnknownDevice, match="TPU v9"):
        work.peak("TPU v9")
    with pytest.raises(work.UnknownDevice):
        work.peak("cpu")
