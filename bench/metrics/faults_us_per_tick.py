"""Device time of the tick's fault context per scan tick: the union of
the sweep program's ops under ``tick/faults`` (this tick's fault rows,
the owner-table diff and the stages' remap invalidation), averaged over
the devices.  Also writes to stderr a ``faults:`` line with its two
sub-scopes, ``inval`` (the owner-table diff) and ``remap`` (the
stages' ``on_fault`` remap and its count of the live entries dropped),
and the counters of the cell's fault program
(``repro.core.faults.counters`` of its compiled schedule)."""

import phasecalc

FAULTS = "tick/faults"


def _config(cell):
    """The cell's ``SimConfig``, built as ``midasbench.cell`` builds it
    for the sweep (whose spec is gone by the time readers run), so that
    the fault compiler's cache holds its schedule."""
    from repro.core import SimConfig

    sim, tr = cell.config["sim"], cell.traffic
    return SimConfig(
        **{k: tuple(v) if isinstance(v, list) else v for k, v in sim.items()},
        policy=tr["policy"],
        middleware=tuple(tr["middleware"]),
        controller=tr.get("controller", "hysteresis"),
        fixed_d=int(tr.get("fixed_d", 2)),
    )


def read(ctx):
    v = phasecalc.us_per_tick(ctx, FAULTS)
    if v is None:
        return None
    from repro.core import faults

    split = "; ".join(
        f"{sub} {phasecalc.us_per_tick(ctx, FAULTS, sub)!r}"
        for sub in ("inval", "remap")
    )
    cell = ctx.cell
    counts = faults.counters(
        faults.compile_faults(_config(cell), cell.T), int(cell.traffic["R"])
    )
    ctx.note(
        f"faults: {FAULTS} {v!r} us/tick ({split}); counters "
        + ", ".join(f"{k} {counts[k]}" for k in sorted(counts))
    )
    return v
