"""Time per sweep in the section III-B warmup that ``run_sweep`` runs
for an adaptive policy: the ``sweep/warmup`` span.  Cells whose traffic
pins the targets run no warmup and report nothing."""


def read(ctx):
    if not ctx.cell.traffic.get("warmup"):
        return None
    durs = [e["dur"] for e in ctx.spans if e["name"] == "sweep/warmup"]
    if not durs or not ctx.n_sweeps:
        return None
    return sum(durs) / 1e3 / ctx.n_sweeps
