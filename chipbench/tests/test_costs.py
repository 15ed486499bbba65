"""costs.py for one known shape, and the peaks table's error."""

import pytest

from chipbench import costs


def test_scan_bytes_of_a_full_group():
    # 4,096 pages x 1,024 entries; 16 slots of int8 key + int16 value;
    # 13 B of entry columns: 61 B per entry
    assert costs.scan_bytes(4096, 16, 16, 13_515) == 4096 * 1024 * 61
    # 4 slots, 103 values: both ids fit int8 -> 4*2 + 13 = 21 B
    assert costs.scan_bytes(64, 4, 4, 103) == 64 * 1024 * 21
    assert costs.id_width(127) == 1 and costs.id_width(128) == 2
    assert costs.id_width(32_767) == 2 and costs.id_width(32_768) == 4


def test_roofline_and_unknown_device():
    assert costs.roofline_s(819e9, "TPU v5 lite") == pytest.approx(1.0)
    with pytest.raises(KeyError):
        costs.peaks("cpu")
    with pytest.raises(KeyError):
        costs.peaks("_source")
