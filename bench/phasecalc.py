"""Device time of the sweep program's phases, read through the
op-to-phase map that the program exports.

The sweep program runs each phase of its tick under a named scope
(``repro.obs.trace.PROGRAM_PHASES``: ``tick/faults``,
``tick/middleware``, ``tick/route``, ``tick/queues``, ``tick/control``,
``tick/summary``, and ``sweep/feasible`` before the scan) and registers
every sweep executable it compiles.  ``repro.obs.trace.phase_map()``
maps each registered program's instructions to (phase, sub-scope).  A
trace's op carries its instruction's name and its module's name
(``jit__run_scan_sweep(<id>)``), so a phase's time is the union of the
intervals of the sweep program's ops in that phase: a loop's op spans
its body's ops, and the union counts the two once.

A program that exports no map gives nothing: each function here then
returns ``None`` and says why in a note.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

SWEEP = "run_scan_sweep"

# the phases a per-layer metric reads
ROUTE, MIDDLEWARE, CONTROL, FEASIBLE = (
    "tick/route", "tick/middleware", "tick/control", "sweep/feasible",
)


def _base(module: str) -> str:
    return module.split("(", 1)[0]


def sweep_map(ctx) -> Optional[Tuple[str, Dict[str, Tuple[str, str]]]]:
    """(module, {instruction: (phase, sub-scope)}) of the one sweep
    program that ran in the window, or ``None`` (with a note) when the
    program exports no map, when two different programs of that name
    ran, or when the map of the one that ran is not known."""
    if hasattr(ctx, "_phasecalc_map"):
        return ctx._phasecalc_map
    ctx._phasecalc_map = None
    if ctx.trace is None:
        return None
    from repro.obs import trace as obs

    if not hasattr(obs, "phase_map"):
        ctx.note("phases: the program exports no op-to-phase map")
        return None
    mods = {
        o.module
        for ops in ctx.trace.devices.values()
        for o in ops
        if SWEEP in o.module and o.end > ctx.lo and o.start < ctx.hi
    }
    if not mods:
        return None
    if len(mods) > 1:
        ctx.note(f"phases: {len(mods)} different sweep programs ran in "
                 f"the window ({', '.join(sorted(mods))}); no phase read")
        return None
    (mod,) = mods
    maps = obs.phase_map()
    key = mod if mod in maps else _base(mod)
    if key not in maps:
        ctx.note(f"phases: no map registered for {mod}")
        return None
    if maps[key] is None:
        ctx.note(f"phases: two different programs were registered as "
                 f"{key}; no phase read")
        return None
    ctx._phasecalc_map = (mod, maps[key])
    return ctx._phasecalc_map


def ticks(ctx) -> int:
    return ctx.n_sweeps * ctx.cell.T


def phase_ns(ctx, phase: str, sub: Optional[str] = None) -> Optional[float]:
    """Device ns of the sweep program's ops in ``phase`` (and, when
    given, in sub-scope ``sub`` or below it), a union of intervals."""
    got = sweep_map(ctx)
    if got is None:
        return None
    mod, pmap = got

    def select(op) -> bool:
        if op.module != mod:
            return False
        ph = pmap.get(op.name)
        if ph is None or ph[0] != phase:
            return False
        return sub is None or ph[1] == sub or ph[1].startswith(sub + "/")

    return ctx.busy_ns(select)


def us_per_tick(ctx, phase: str, sub: Optional[str] = None):
    """Device us of ``phase`` per scan tick, or ``None`` where the phase
    ran no op (or no map could be read)."""
    ns = phase_ns(ctx, phase, sub)
    if not ns or ns <= 0 or not ticks(ctx):
        return None
    return ns / 1e3 / ticks(ctx)


def breakdown(ctx) -> Optional[str]:
    """One line: every phase and sub-scope's us per tick, what of the
    sweep program's busy time no phase owns and its three largest ops
    by self time, and the compile cache's hits and misses at set-up."""
    got = sweep_map(ctx)
    if got is None:
        return None
    mod, pmap = got
    from midasbench import tracecalc

    n = ticks(ctx)
    keys = sorted(set(pmap.values()))
    parts, total = [], 0.0
    for phase in sorted({k[0] for k in keys}):
        v = us_per_tick(ctx, phase)
        if v is None:
            continue
        total += v
        subs = []
        for ph, sub in keys:
            if ph == phase and sub:
                s = us_per_tick(ctx, phase, sub)
                if s is not None:
                    subs.append(f"{sub} {s!r}")
        parts.append(f"{phase} {v!r}" + (f" ({'; '.join(subs)})"
                                          if subs else ""))
    in_sweep = ctx.busy_ns(lambda op: op.module == mod)
    owned = ctx.busy_ns(
        lambda op: op.module == mod and op.name in pmap
    )
    rest = {}
    for ops in ctx.trace.devices.values():
        inside = [o for o in ops if o.start >= ctx.lo and o.end <= ctx.hi]
        for o, t in zip(inside, tracecalc.self_times(inside)):
            if o.module == mod and o.name not in pmap:
                rest[o.name] = rest.get(o.name, 0.0) + t
    k = max(len(ctx.trace.devices), 1)
    top = sorted(rest.items(), key=lambda kv: -kv[1])[:3]
    line = (
        "phases us/tick: " + "; ".join(parts)
        + f"; phases together {total!r}"
        + f"; unattributed {(in_sweep - owned) / 1e3 / n!r} of "
        f"{in_sweep / 1e3 / n!r} ("
        + ", ".join(f"{name} {t / k / 1e3 / n!r}" for name, t in top)
        + ")"
    )
    from repro.obs import trace as obs

    at = getattr(obs.RECORDER, "compile_at_reset", None)
    if at:
        line += (f"; compile cache hits {at['cache_hits']}, misses "
                 f"{at['cache_misses']}")
    return line
