"""The chip peaks table and the least-bytes work model."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import peaks  # noqa: E402


def test_v5e_peaks_are_the_published_ones():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["ici_bits_per_s"] == 1600e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


@pytest.mark.parametrize("n, live, links, want", [
    # 8 processes x 4 live columns x 2 bits = 8 bytes; 16 links x 4 B
    (8, 4, 16, 8 + 64),
    # one process, one column: a quarter byte, plus one link
    (1, 1, 1, 0.25 + 4),
    # no live column: nothing to move
    (1 << 20, 0, 4 << 20, 0.0),
])
def test_least_bytes_per_round_by_hand(n, live, links, want):
    assert peaks.least_bytes_per_round(n, live, links) == want
