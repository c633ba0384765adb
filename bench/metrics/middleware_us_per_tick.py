"""Device time of the tick's middleware stages per scan tick: the union
of the sweep program's ops under ``tick/middleware`` (the cooperative
or fleet cache), averaged over the devices."""

import phasecalc


def read(ctx):
    return phasecalc.us_per_tick(ctx, phasecalc.MIDDLEWARE)
