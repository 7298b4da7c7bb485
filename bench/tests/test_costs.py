"""Byte counts and the peak table."""

import pytest

from bench import costs


def test_bytes_per_task():
    assert costs.prefix_scan_bytes(1000) == 8000


def test_least_seconds_at_v5e_bandwidth():
    assert costs.least_seconds(819_000_000, "TPU v5 lite") == \
        pytest.approx(1e-3)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="TPU v9"):
        costs.least_seconds(1, "TPU v9")
