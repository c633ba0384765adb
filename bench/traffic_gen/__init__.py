"""Traffic generators of the benchmark: the program receives only the
grids these build.

A copy of the generators the cells use (``light``, ``skewed``,
``bursty``, the four combinators, and the ``rename_storm``,
``flash_crowd`` and ``job_startup`` scenarios), kept here so that a
change to the program's own workload package cannot move the yardstick.
``make(name, ...)`` returns a :class:`Grid` of ``(T, R)`` arrays: request
keys in ``[0, N)``, a validity mask (a prefix of each row) and a write
flag.
"""

from traffic_gen.gen import GENERATORS, Grid, make

__all__ = ["GENERATORS", "Grid", "make"]
