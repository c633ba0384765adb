"""The routing kernel's work count and the table of chip peaks."""

from pathlib import Path

import pytest

from midasbench import peaks
from midasbench.spec import Bench

BENCH = Bench.from_root(Path(__file__).resolve().parent.parent)


def test_route_select_count_for_a_known_shape():
    cost = BENCH.cost("route_select").cost
    # 3 requests of d_max=4 candidates in one grid cell of m=8 servers:
    # 6 ops per candidate; per request 4*(4+4+1) + 4 + 1 = 41 bytes; the
    # two (8,) float32 views read once
    assert cost(3, 1, 4, 8) == (72, 3 * 41 + 64)
    ops, nbytes = cost(512 * 32 * 240, 128 * 32 * 240, 4, 64)
    assert ops == 512 * 32 * 240 * 24
    assert nbytes == 512 * 32 * 240 * 41 + 128 * 32 * 240 * 512


def test_peaks_of_a_known_chip():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_a_chip_missing_from_the_table_raises():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("TPU v99 imaginary")
