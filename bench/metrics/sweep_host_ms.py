"""Host time of the sweep API per sweep: the ``sweep/init_states`` and
``sweep/host_slice`` spans of ``repro.core.sweep.run_sweep``."""

SPANS = ("sweep/init_states", "sweep/host_slice")


def read(ctx):
    durs = [e["dur"] for e in ctx.spans if e["name"] in SPANS]
    if not durs or not ctx.n_sweeps:
        return None
    return sum(durs) / 1e3 / ctx.n_sweeps
