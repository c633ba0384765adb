"""Work of one routing-kernel call, from the routing problem's shapes.

Per request: its ``d_max`` candidate ids (int32), their tie-break scores
(float32) and sampling flags (one byte each) are read, and its
assignment (int32) and has-candidate flag (one byte) are written; the
two (m,) telemetry views (float32) are read once per grid cell.  Each
candidate costs a few operations: two gathers' compares against the
primary's margins, the sampling and, the tie-break add, and the running
minimum.  The count is of the routing problem and not of how a kernel
computes it (a one-hot contraction over the m servers is not counted),
so a kernel that routes the same requests another way is judged against
the same work.
"""

OPS_PER_CANDIDATE = 6


def cost(requests: int, view_reads: int, d_max: int, m: int):
    """(operations, bytes) of routing ``requests`` requests, with the
    telemetry views of one grid cell read ``view_reads`` times (once per
    cell per routing wave)."""
    ops = requests * d_max * OPS_PER_CANDIDATE
    per_request = d_max * (4 + 4 + 1) + 4 + 1
    nbytes = requests * per_request + view_reads * 2 * m * 4
    return ops, nbytes
