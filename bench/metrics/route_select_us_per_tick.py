"""Device time of the routing kernel (``midas_route.route_select``) per
scan tick: the summed durations of its operations, averaged over the
devices."""


def read(ctx):
    ns, n = ctx.kernel_ns("route_select")
    ticks = ctx.n_sweeps * ctx.cell.T
    if not n or not ticks:
        return None
    return ns / 1e3 / ticks
