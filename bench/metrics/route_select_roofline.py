"""Share of the routing kernel's roofline: the least time the chip
could take for the routing work in the window (the larger of its
operations over peak compute and its bytes over peak HBM bandwidth,
``cost/route_select.py``) over the kernel's device time."""


def read(ctx):
    ns, n = ctx.kernel_ns("route_select")
    if not n or ns <= 0:
        return None
    dep = ctx.cell.dep
    T, R = ctx.cell.T, int(ctx.cell.traffic["R"])
    per_device_cells = ctx.cell.grid_cells / ctx.devices
    requests = ctx.n_sweeps * T * per_device_cells * R
    views = ctx.n_sweeps * T * dep.waves * per_device_cells
    ops, nbytes = ctx.cost("route_select").cost(
        int(requests), int(views), dep.d_max, dep.m
    )
    t_ops = ops / ctx.peaks["flops_per_s"]
    t_bytes = nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.note(
        "route_select_roofline bound by "
        + ("operations" if t_ops > t_bytes else "bytes")
    )
    return 100.0 * max(t_ops, t_bytes) / (ns / 1e9)
