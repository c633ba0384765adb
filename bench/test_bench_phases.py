"""The per-layer metrics that read the program's phases and compile
counters, on hand-made operations with a hand-made op-to-phase map: a
loop's op and its body's ops count once, a program without a map or
with two programs under one name gives nothing, and the set-up metrics
read the totals kept at the reset.  Then on a trace recorded on a TPU
v5e (``data/small_sweep_scoped.*``: two sweeps of a 12-tick testbed
grid, midas behind the cache, two scenarios x two seeds), beside the
program's HLO text and the op-to-phase map it exported."""

import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import phasecalc
import run as runner
from midasbench import tracecalc as tc
from midasbench.spec import Bench
from repro.obs import trace as obs

REAL = Bench.from_root(runner.ROOT)
MOD = "jit__run_scan_sweep(7)"
T = 10
US = 1000.0  # ns


def _ops(module=MOD):
    op = tc.Op
    return [
        op("while.1", 0, 100 * US, module),  # the tick scan: no phase
        op("while.2", 10 * US, 40 * US, module),  # the wave scan
        op("route_select.3", 12 * US, 20 * US, module),
        op("fusion.4", 22 * US, 30 * US, module),
        op("fusion.5", 40 * US, 50 * US, module),
        op("dynamic-update-slice.6", 50 * US, 60 * US, module),
        op("conditional.7", 60 * US, 80 * US, module),  # the slow loop
        op("fusion.8", 65 * US, 70 * US, module),
        op("copy.10", 90 * US, 95 * US, module),  # no phase
        op("fusion.9", 100 * US, 110 * US, module),
        op("fusion.1", 110 * US, 120 * US, "jit_other(3)"),
    ]


PMAP = {
    "while.2": ("tick/route", ""),
    "route_select.3": ("tick/route", ""),
    "fusion.4": ("tick/route", ""),
    "fusion.5": ("tick/middleware", "fleet_cache/scatter"),
    "dynamic-update-slice.6": ("tick/middleware", "fleet_cache/snapshot"),
    "conditional.7": ("tick/control", ""),
    "fusion.8": ("tick/control", ""),
    "fusion.9": ("sweep/feasible", ""),
}


def _ctx(ops=None):
    trace = tc.Trace({"/device:TPU:0": ops or _ops()},
                     [tc.Span("bench/window", 0, 200 * US)])
    return runner.Ctx(
        trace=trace, lo=0, hi=200 * US, spans=[], n_sweeps=1,
        cell=SimpleNamespace(T=T), bench=REAL, peaks={}, devices=1,
        notes=[],
    )


def _read(metric, ctx):
    return REAL.reader(metric).read(ctx)


@pytest.fixture
def program(monkeypatch):
    """The program exports ``PMAP`` for the sweep module, by the base
    name a trace's module carries before its id."""
    maps = {"jit__run_scan_sweep": PMAP}
    monkeypatch.setattr(obs, "phase_map", lambda: maps)
    return maps


@pytest.mark.parametrize(
    "metric,us",
    [
        ("routing_us_per_tick", 3.0),  # [10, 40): the nested ops once
        ("middleware_us_per_tick", 2.0),
        ("control_us_per_tick", 2.0),  # [60, 80)
        ("feasible_us_per_tick", 1.0),
    ],
)
def test_each_phase_reader_takes_the_union_of_its_ops(program, metric, us):
    assert _read(metric, _ctx()) == pytest.approx(us)


def test_sub_scopes_split_the_fleet_stage(program):
    ctx = _ctx()
    mw = phasecalc.MIDDLEWARE
    assert phasecalc.us_per_tick(ctx, mw, "fleet_cache/scatter") == 1.0
    assert phasecalc.us_per_tick(ctx, mw, "fleet_cache/snapshot") == 1.0
    assert phasecalc.us_per_tick(ctx, mw, "fleet_cache") == 2.0
    assert phasecalc.us_per_tick(ctx, mw, "cache") is None


def test_a_map_keyed_by_the_traces_module_name_is_used(monkeypatch):
    monkeypatch.setattr(obs, "phase_map", lambda: {MOD: PMAP})
    assert _read("routing_us_per_tick", _ctx()) == pytest.approx(3.0)


def test_a_phase_that_ran_no_op_gives_nothing(program):
    ops = [o for o in _ops() if not o.name.startswith(("fusion.5",
                                                       "dynamic"))]
    assert _read("middleware_us_per_tick", _ctx(ops)) is None


def test_the_routing_reader_notes_the_whole_breakdown(program):
    ctx = _ctx()
    _read("routing_us_per_tick", ctx)
    (line,) = ctx._notes
    assert "tick/route 3.0" in line
    assert "fleet_cache/scatter 1.0" in line
    # the sweep is busy [0, 110): 11 us/tick, of which 8 have a phase
    assert "unattributed 3.0 of 11.0" in line
    assert "while.1" in line and "copy.10" in line


def test_a_program_without_a_map_gives_nothing(monkeypatch):
    monkeypatch.delattr(obs, "phase_map", raising=False)
    ctx = _ctx()
    for metric in ("routing_us_per_tick", "middleware_us_per_tick",
                   "control_us_per_tick", "feasible_us_per_tick"):
        assert _read(metric, ctx) is None
    assert any("no op-to-phase map" in n for n in ctx._notes)


def test_two_programs_of_one_name_in_the_window_give_nothing(program):
    ops = _ops() + [tc.Op("fusion.4", 150 * US, 160 * US,
                          "jit__run_scan_sweep(8)")]
    ctx = _ctx(ops)
    assert _read("routing_us_per_tick", ctx) is None
    assert any("2 different sweep programs" in n for n in ctx._notes)


def test_two_programs_registered_under_one_name_give_nothing(program):
    program["jit__run_scan_sweep"] = None
    ctx = _ctx()
    assert _read("control_us_per_tick", ctx) is None
    assert any("two different programs" in n for n in ctx._notes)


def test_a_program_not_registered_gives_nothing(monkeypatch):
    monkeypatch.setattr(obs, "phase_map", lambda: {"jit__run_scan": {}})
    ctx = _ctx()
    assert _read("feasible_us_per_tick", ctx) is None
    assert any("no map registered" in n for n in ctx._notes)


@pytest.mark.parametrize(
    "metric,key", [("setup_trace_lower_s", "trace_lower_s"),
                   ("setup_compile_s", "compile_load_s")],
)
def test_the_setup_readers_read_the_totals_at_the_reset(
    monkeypatch, metric, key
):
    at = {"trace_lower_s": 12.5, "compile_load_s": 30.25}
    monkeypatch.setattr(obs.RECORDER, "compile_at_reset", at)
    assert _read(metric, _ctx()) == at[key]
    monkeypatch.setattr(obs.RECORDER, "compile_at_reset", None)
    assert _read(metric, _ctx()) is None
    # a program whose recorder keeps no counters
    monkeypatch.delattr(obs.RECORDER, "compile_at_reset")
    assert _read(metric, _ctx()) is None


DATA = Path(__file__).resolve().parent / "data"
SCOPED = "small_sweep_scoped"


def _scoped():
    doc = json.loads((DATA / f"{SCOPED}.phases.json").read_text())
    pmap = {k: tuple(v) for k, v in doc["phases"].items()}
    trace = tc.load(DATA / f"{SCOPED}.xplane.pb.gz")
    lo, hi = tc.window(trace)
    ctx = runner.Ctx(
        trace=trace, lo=lo, hi=hi, spans=[], n_sweeps=doc["n_sweeps"],
        cell=SimpleNamespace(T=doc["T"]), bench=REAL, peaks={}, devices=1,
        notes=[],
    )
    return doc, pmap, ctx


def test_a_scoped_trace_recorded_on_the_chip(monkeypatch):
    doc, pmap, ctx = _scoped()
    monkeypatch.setattr(obs, "phase_map", lambda: {doc["module"]: pmap})
    phases = {m: _read(m, ctx) for m in (
        "routing_us_per_tick", "middleware_us_per_tick",
        "control_us_per_tick", "feasible_us_per_tick",
    )}
    assert all(v > 0 for v in phases.values()), phases
    summary = phasecalc.us_per_tick(ctx, "tick/summary")
    scan = _read("scan_us_per_tick", ctx)
    assert sum(phases.values()) + summary <= scan
    # the routing kernel runs inside the routing phase
    assert _read("route_select_us_per_tick", ctx) < phases[
        "routing_us_per_tick"
    ]
    assert "cache" in ctx._notes[0] and "unattributed" in ctx._notes[0]
    assert {"sweep/dispatch", "sweep/transfer"} <= {
        s.name for s in ctx.trace.host
    }


def test_the_exported_map_is_the_parse_of_the_programs_hlo():
    doc, pmap, _ = _scoped()
    with gzip.open(DATA / f"{SCOPED}.hlo.txt.gz", "rt") as f:
        parsed = obs.parse_phases(f.read())
    assert {k: parsed.get(k) for k in pmap} == pmap
