"""Device busy time of the sweep program (``_run_scan_sweep``, or its
sharded form) per scan tick, averaged over the devices."""


def read(ctx):
    if ctx.trace is None:
        return None
    ns = ctx.busy_ns(lambda op: "run_scan_sweep" in op.module)
    ticks = ctx.n_sweeps * ctx.cell.T
    if ns <= 0 or not ticks:
        return None
    return ns / 1e3 / ticks
