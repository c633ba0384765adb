"""Share of the traced window in which no operation ran on the device,
averaged over the cell's devices."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices or ctx.window_ns <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_ns() / ctx.window_ns)
