"""Device time of the tick's routing phase per scan tick: the union of
the sweep program's ops under ``tick/route`` (the wave split, the wave
scan and the routing kernel in it), averaged over the devices.  Also
writes the whole phase breakdown to stderr."""

import phasecalc


def read(ctx):
    line = phasecalc.breakdown(ctx)
    if line:
        ctx.note(line)
    return phasecalc.us_per_tick(ctx, phasecalc.ROUTE)
