"""The numbers that decide ``correct``, each against its own limit.

* ``row_gap``: over the sampled grid cells and every field of their
  summary rows (the fields the cell's reference compares: in
  ``midas_queue`` the queue sums and maximum, the CV sums, both
  histograms, arrivals, steered, eligible, cache hits, and the per-tick
  knob and mean-queue trajectories), the widest relative L1 gap between
  the program's row and the plain reference's:
  ``sum|program - reference| / max(sum|reference|, 1)``.
* ``rows_differing``: rows of the window's later sweeps that are not
  bitwise equal to the first sweep's row of the same grid cell (every
  sweep in the window runs the same grid from the same seeds).
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

import numpy as np


Row = Dict[str, np.ndarray]


def field_gap(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    return float(np.abs(a - b).sum() / max(np.abs(b).sum(), 1.0))


def row_gap(prog: Row, ref: Row) -> Tuple[float, str]:
    """Widest field gap of one row, and the field it is in."""
    gaps = {f: field_gap(prog[f], ref[f]) for f in ref}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def rows_equal(a: Row, b: Row) -> bool:
    return all(
        np.array_equal(np.asarray(a[f]), np.asarray(b[f])) for f in a
    )


def rows_differing(first: Dict, later: Iterable[Dict]) -> int:
    """Rows of later sweeps that differ from the first sweep's."""
    return sum(
        not rows_equal(first[c], rows[c]) for rows in later for c in first
    )


def verdict(
    numbers: Dict[str, float], limits: Dict[str, float]
) -> Tuple[bool, List[str]]:
    """``correct`` and the names of the numbers over their limits."""
    missing = set(limits) ^ set(numbers)
    if missing:
        raise ValueError(f"numbers and limits differ: {sorted(missing)}")
    over = [k for k in limits if not numbers[k] <= limits[k]]
    return not over, over


def report_lines(numbers, limits) -> List[str]:
    return [
        f"check {k}: {numbers[k]!r} (limit {limits[k]!r})" for k in limits
    ]
