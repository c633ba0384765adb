"""Device time of the tick's control loop per scan tick: the union of
the sweep program's ops under ``tick/control`` (write-mix window,
latency sketch, staggered EWMA, the fast and the slow loop), averaged
over the devices."""

import phasecalc


def read(ctx):
    return phasecalc.us_per_tick(ctx, phasecalc.CONTROL)
