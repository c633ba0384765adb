"""Device time of the feasible-set gather hoisted before the scan
(``sweep/feasible``: one batched ``hashring.feasible_set`` per
workload and sweep), per scan tick, averaged over the devices."""

import phasecalc


def read(ctx):
    return phasecalc.us_per_tick(ctx, phasecalc.FEASIBLE)
