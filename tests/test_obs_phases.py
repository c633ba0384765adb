"""The recorder's view inside the sweep program (DESIGN.md §13).

* **Phases** — the sweep program registers itself when it compiles, and
  its op-to-phase map names every phase of the tick that the
  configuration runs: ``tick/middleware`` only behind a cache,
  ``tick/faults`` only under a fault program, the fleet cache's
  ``scatter`` and ``snapshot`` sub-scopes under its stage.
* **No cost in results or traces** — registering adds no trace of the
  sweep program (``_SWEEP_TRACES`` and ``_SHARD_TRACES`` stay put), and
  rows are bitwise those of a run with no scopes and no registration.
* **Compile counters** — seconds are a union of intervals (a nested
  trace counts once), ``configure(fresh=True)`` keeps the totals as
  they were at the reset, and JAX's own compile events reach the
  global recorder.
"""

import contextlib
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import FaultEvent, SimConfig, SweepSpec, run_sweep
from repro.core import make_workload, sim
from repro.core import sweep as sweep_lib
from repro.obs import trace as trace_lib

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
T, M = 12, 8
TICK = {p for p in trace_lib.PROGRAM_PHASES if p.startswith("tick/")}
FLEET = dict(P=8, n_groups=8, fleet_routing=True, gossip_ms=100.0)


def _spec(metrics="summary", T=T, **kw):
    kw.setdefault("N", 4096)
    cfg = SimConfig(m=M, policy="midas", **kw)
    wls = tuple(make_workload(n, T=T, m=M, seed=0, N=kw["N"])
                for n in ("bursty", "rename_storm"))
    return SweepSpec(config=cfg, workloads=wls, seeds=(1, 2),
                     metrics=metrics, targets=(0.5, 400.0))


@pytest.fixture
def registry(monkeypatch):
    """An empty program registry and empty jit caches, so the next
    sweep compiles and is the one program registered."""
    monkeypatch.setattr(trace_lib, "_PROGRAMS", {})
    jax.clear_caches()
    yield
    jax.clear_caches()


def _phases(name="jit__run_scan_sweep"):
    pmap = trace_lib.phase_map()[name]
    return {ph for ph, _ in pmap.values()}, {
        sub for ph, sub in pmap.values() if ph == "tick/middleware"
    }


@pytest.mark.parametrize(
    "kw,faulted,middleware",
    [
        (dict(middleware=("cache",)), False, {"cache"}),
        (dict(middleware=("fleet_cache",), **FLEET), False,
         {"fleet_cache", "fleet_cache/scatter", "fleet_cache/snapshot"}),
        (dict(middleware=()), False, set()),
        # a crash detected inside the horizon: the ring's owners change
        (dict(middleware=("cache",), T=40,
              faults=(FaultEvent("proxy_crash", t0=1, duration=0),)),
         True, {"cache"}),
    ],
    ids=["cache", "fleet_cache", "no_middleware", "faulted"],
)
def test_the_phase_map_names_each_phase_the_sweep_runs(
    registry, kw, faulted, middleware
):
    run_sweep(_spec(**kw))
    assert list(trace_lib._PROGRAMS) == ["jit__run_scan_sweep"]
    phases, subs = _phases()
    want = TICK - {"tick/faults", "tick/middleware"}
    want |= {"sweep/feasible"}
    if middleware:
        want.add("tick/middleware")
    if faulted:
        want.add("tick/faults")
    assert phases == want
    assert subs == middleware


def test_a_full_metrics_sweep_has_no_summary_phase(registry):
    run_sweep(_spec(metrics="full", middleware=("cache",)))
    phases, _ = _phases()
    assert "tick/summary" not in phases
    assert {"tick/route", "tick/control", "sweep/feasible"} <= phases


def test_registering_adds_no_trace_and_a_warm_call_registers_nothing(
    registry,
):
    spec = _spec(middleware=("cache",))
    before = sim._SWEEP_TRACES[0], sweep_lib._SHARD_TRACES[0]
    run_sweep(spec)
    assert sim._SWEEP_TRACES[0] == before[0] + 1
    assert sweep_lib._SHARD_TRACES[0] == before[1]
    exe = trace_lib._PROGRAMS["jit__run_scan_sweep"]["exe"]
    run_sweep(spec)
    assert sim._SWEEP_TRACES[0] == before[0] + 1
    assert trace_lib._PROGRAMS["jit__run_scan_sweep"]["exe"] is exe


def test_two_programs_under_one_name_map_to_none(registry):
    run_sweep(_spec(middleware=("cache",)))
    run_sweep(_spec(middleware=()))
    assert trace_lib.phase_map() == {"jit__run_scan_sweep": None}


def test_the_sharded_sweep_registers_without_a_trace():
    code = """
        from repro.core import SimConfig, SweepSpec, run_sweep
        from repro.core import make_workload
        from repro.core import sim
        from repro.core.sweep import _SHARD_TRACES
        from repro.obs import trace

        wls = (make_workload("bursty", T=12, m=4, seed=0),)
        spec = SweepSpec(config=SimConfig(m=4, middleware=("cache",)),
                         workloads=wls, seeds=(0, 1, 2), devices=2,
                         metrics="summary", do_warmup=False)
        run_sweep(spec)
        run_sweep(spec)
        assert _SHARD_TRACES[0] == 1 and sim._SWEEP_TRACES[0] == 0
        pmap = trace.phase_map()["jit__run_scan_sweep_sharded"]
        print(sorted({ph for ph, _ in pmap.values()}))
    """
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=480)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "'tick/middleware'" in out.stdout
    assert "'sweep/feasible'" in out.stdout


@pytest.mark.parametrize(
    "kw",
    [dict(middleware=("cache",)),
     dict(middleware=("fleet_cache",), **FLEET)],
    ids=["cache", "fleet_cache"],
)
@pytest.mark.parametrize("metrics", ["summary", "full"])
def test_rows_are_bitwise_those_of_a_run_without_scopes(
    registry, monkeypatch, kw, metrics
):
    spec = _spec(metrics=metrics, **kw)
    with monkeypatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        mp.setattr(trace_lib.RECORDER, "enabled", False)
        bare = run_sweep(spec)
    assert trace_lib.phase_map() == {}
    jax.clear_caches()
    scoped = run_sweep(spec)
    assert list(trace_lib.phase_map()) == ["jit__run_scan_sweep"]
    assert set(bare.cells) == set(scoped.cells)
    for c in bare.cells:
        a, b = bare.cells[c], scoped.cells[c]
        fields = (a._fields if hasattr(a, "_fields")
                  else [f.name for f in dataclasses.fields(a)])
        for field in fields:
            if field in ("config", "final_cache"):
                continue
            x, y = getattr(a, field), getattr(b, field)
            if x is None or y is None:
                assert x is y, (c, field)
                continue
            assert np.array_equal(np.asarray(x), np.asarray(y)), (c, field)


def test_phase_of_reads_scopes_through_transform_wrappers():
    pre = "jit(_run_scan_sweep)/vmap(vmap(while))/body/closed_call/"
    assert trace_lib.phase_of(pre + "tick/route/add") == ("tick/route", "")
    assert trace_lib.phase_of(
        pre + "tick/middleware/fleet_cache/scatter/scatter"
    ) == ("tick/middleware", "fleet_cache/scatter")
    # the last component is the primitive even where it shares a name
    assert trace_lib.phase_of(
        pre + "tick/middleware/fleet_cache/scatter"
    ) == ("tick/middleware", "fleet_cache")
    # what JAX itself puts in a name stack is no sub-scope
    assert trace_lib.phase_of(
        pre + "tick/middleware/fleet_cache/remainder/_where/select_n"
    ) == ("tick/middleware", "fleet_cache")
    assert trace_lib.phase_of(
        pre + "tick/control/cond/branch_1_fun/_uniform/add"
    ) == ("tick/control", "")
    # the outermost phase wins: a stage's hook in the slow loop is control
    assert trace_lib.phase_of(
        pre + "tick/control/cond/branch_1/tick/middleware/cache/mul"
    ) == ("tick/control", "")
    assert trace_lib.phase_of(
        "jit(_run_scan_sweep)/vmap(vmap(sweep/feasible))/jit(x)/add"
    ) == ("sweep/feasible", "")
    assert trace_lib.phase_of("jit(_run_scan_sweep)/while") is None


@pytest.mark.parametrize(
    "scope,want",
    [
        ("tick/faults/inval/ne", ("tick/faults", "inval")),
        ("tick/faults/inval/jit(_where)/select_n", ("tick/faults", "inval")),
        ("tick/faults/remap/select_n", ("tick/faults", "remap")),
        ("tick/faults/remap/fleet_cache/select_n", ("tick/faults", "remap")),
        # the last component is the primitive, and JAX's own scopes are
        # no sub-scope of the fault phase
        ("tick/faults/remap", ("tick/faults", "")),
        ("tick/faults/_where/select_n", ("tick/faults", "")),
        # the middleware's sub-scopes map as before
        ("tick/middleware/fleet_cache/snapshot/dynamic_update_slice",
         ("tick/middleware", "fleet_cache/snapshot")),
        ("tick/middleware/cache/inval/add", ("tick/middleware", "cache")),
    ],
)
def test_ops_under_the_fault_scopes_map_to_them(scope, want):
    pre = "jit(_run_scan_sweep)/vmap(vmap(while))/body/closed_call/"
    assert trace_lib.phase_of(pre + scope) == want


def _lowered_digest(cfg):
    """sha256 of the sweep program as lowered for ``cfg`` (StableHLO
    text with no source locations): what the compiler is given."""
    import hashlib

    import jax.numpy as jnp

    wls = [make_workload(n, T=T, m=M, seed=0, N=cfg.N)
           for n in ("bursty", "rename_storm")]
    states = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[sim.init_state(dataclasses.replace(cfg, seed=s), 0.5, 400.0)
          for s in (1, 2)],
    )
    grids = [jnp.stack([getattr(w, f) for w in wls])
             for f in ("keys", "mask", "is_write")]
    text = sim._run_scan_sweep.lower(cfg, states, *grids, "summary").as_text()
    return hashlib.sha256(text.encode()).hexdigest()


# the digests of the fault-free programs as lowered before the fault
# phase gained its sub-scopes and its remap-drop count
FAULT_FREE = {
    "cache": ("4dffc0673bedbfb8c0d4e4a5cbc57abc"
              "556bd55409361159b467909bc784e0f0",
              dict(middleware=("cache",))),
    "fleet_cache": ("29db661a6aafa32f8cc16c4e04b4abcc"
                    "82de81e89a3d3c9f4de72767b50c0300",
                    dict(middleware=("fleet_cache",), **FLEET)),
}


@pytest.mark.parametrize("name", sorted(FAULT_FREE))
def test_a_fault_free_sweep_is_the_program_it_was_and_counts_no_fault(
    registry, name
):
    from repro.core import faults

    digest, kw = FAULT_FREE[name]
    cfg = SimConfig(m=M, N=4096, policy="midas", **kw)
    assert _lowered_digest(cfg) == digest
    # no fault program, so no counters and no remap-drop field
    assert faults.compile_faults(cfg, T) is None
    rows = run_sweep(_spec(T=T, **kw)).cells.values()
    assert all(r.remap_dropped_total is None for r in rows)


def test_a_faulted_sweep_splits_its_fault_phase_and_counts_its_schedule(
    registry
):
    """Under a fault program that changes membership, the fault phase
    has two sub-scopes, the owner-table diff (``inval``) and the stages'
    remap invalidation (``remap``), the row counts the live entries the
    remap dropped, and ``faults.counters`` gives what the compiled
    schedule implies."""
    from repro.core import faults

    events = ("ckpt_storm_fleet:t0=5,duration=20,magnitude=0.6",
              "proxy_crash:t0=8,duration=16,target=0")
    spec = _spec(middleware=("fleet_cache",), T=40, **FLEET, faults=events)
    res = run_sweep(spec)
    pmap = trace_lib.phase_map()["jit__run_scan_sweep"]
    assert {sub for ph, sub in pmap.values() if ph == "tick/faults"} == {
        "inval", "remap"
    }
    assert all(r.remap_dropped_total >= 0 for r in res.cells.values())
    fc = faults.compile_faults(spec.config, 40)
    R = spec.workloads[0].keys.shape[1]
    got = faults.counters(fc, R)
    own = fc.owner_by_epoch
    assert got["epochs"] == 3 == own.shape[0]
    assert got["flip_ticks"] == [18, 24]
    assert got["moved_keys"] == [
        int((own[1] != own[0]).sum()), int((own[2] != own[1]).sum())
    ]
    assert 0 < got["moved_keys"][0] < spec.config.N
    assert got["server_ticks_down"] == 16
    assert got["detect_delay_ticks"] == [fc.timeout_ticks] == [10]
    assert got["storm_ticks"] == 20
    # the trailing 60 % of each tick's slots, over the storm's ticks
    tail = (R - np.arange(R, dtype=np.float32) - 0.5) / R
    assert got["storm_slots"] == 20 * int((tail < np.float32(0.6)).sum())


def test_parse_phases_gives_an_unscoped_fusion_its_fused_phase():
    def meta(op_name):
        return f', metadata={{op_name="jit(f)/{op_name}"}}'

    text = "\n".join([
        "HloModule jit_f, is_scheduled=true",
        "",
        "%fused_computation (p: f32[4]) -> f32[4] {",
        "  %p = f32[4]{0} parameter(0)",
        "  %a = f32[4]{0} add(%p, %p)" + meta("tick/queues/add"),
        "  ROOT %b = f32[4]{0} negate(%a)" + meta("neg"),
        "}",
        "",
        "ENTRY %main (x: f32[4]) -> f32[4] {",
        "  %x = f32[4]{0} parameter(0)",
        "  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, "
        "calls=%fused_computation" + meta("neg"),
        "  ROOT %copy.2 = f32[4]{0} copy(%fusion.1)"
        + meta("tick/route/copy"),
        "}",
    ])
    pmap = trace_lib.parse_phases(text)
    assert pmap["fusion.1"] == ("tick/queues", "")
    assert pmap["copy.2"] == ("tick/route", "")
    assert "x" not in pmap


@pytest.mark.parametrize(
    "body_op,want",
    [(None, ("tick/middleware", "fleet_cache/snapshot")),
     ("tick/middleware/fleet_cache/scatter/add",
      ("tick/middleware", "fleet_cache/scatter"))],
    ids=["users_decide", "body_decides"],
)
def test_parse_phases_gives_a_built_loop_its_body_then_its_users_phase(
    body_op, want
):
    # a loop the compiler built, with no op_name, between a table
    # written under scatter and its read under snapshot
    def meta(op_name):
        if op_name is None:
            return ""
        return f', metadata={{op_name="jit(f)/{op_name}"}}'

    stage = "tick/middleware/fleet_cache/"
    text = "\n".join([
        "HloModule jit_f, is_scheduled=true",
        "",
        "%loop_body (p: (s32[], f32[8])) -> (s32[], f32[8]) {",
        "  %p = (s32[], f32[8]{0}) parameter(0)",
        "  %i = s32[] get-tuple-element(%p), index=0",
        "  %t = f32[8]{0} get-tuple-element(%p), index=1",
        "  %one = s32[] constant(1)",
        "  %j = s32[] add(%i, %one)" + meta(body_op),
        "  ROOT %r = (s32[], f32[8]{0}) tuple(%j, %t)",
        "}",
        "",
        "%loop_cond (q: (s32[], f32[8])) -> pred[] {",
        "  %q = (s32[], f32[8]{0}) parameter(0)",
        "  %k = s32[] get-tuple-element(%q), index=0",
        "  %n = s32[] constant(4)",
        "  ROOT %lt = pred[] compare(%k, %n), direction=LT",
        "}",
        "",
        "ENTRY %main (x: f32[8]) -> f32[8] {",
        "  %x = f32[8]{0} parameter(0)",
        "  %scatter.1 = f32[8]{0} negate(%x)"
        + meta(stage + "scatter/scatter"),
        "  %zero = s32[] constant(0)",
        "  %tuple.1 = (s32[], f32[8]{0}) tuple(%zero, %scatter.1)",
        "  %while.1 = (s32[], f32[8]{0}) while(%tuple.1), "
        "condition=%loop_cond, body=%loop_body",
        "  %gte.1 = f32[8]{0} get-tuple-element(%while.1), index=1",
        "  ROOT %snap.1 = f32[8]{0} copy(%gte.1)"
        + meta(stage + "snapshot/dynamic_update_slice"),
        "}",
    ])
    pmap = trace_lib.parse_phases(text)
    assert pmap["scatter.1"] == ("tick/middleware", "fleet_cache/scatter")
    assert pmap["snap.1"] == ("tick/middleware", "fleet_cache/snapshot")
    assert pmap["while.1"] == want
    # what the loop runs is the loop's
    assert pmap["r"] == pmap["lt"] == want
    assert "gte.1" not in pmap and "tuple.1" not in pmap


# ---------------------------------------------------------------------------
# Compile counters
# ---------------------------------------------------------------------------


def test_importing_the_recorder_loads_no_jax():
    # a parent that launches chip workers imports the harness helpers
    # and must leave the chip to them
    code = """
        import sys
        import benchmarks.common
        import repro.obs.report
        from repro.obs import trace
        trace.configure(fresh=True)
        with trace.span("x"):
            pass
        jax = sorted(m for m in sys.modules if m.split(".")[0] == "jax")
        assert not jax, jax
    """
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([SRC, root]))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, cwd=root,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-4000:]


def test_compile_counters_count_nested_intervals_once():
    cc = trace_lib.CompileCounters()
    cc.add("trace", 2.0, 5.0)  # inner trace, reported first
    cc.add("trace", 0.0, 10.0)  # the outer trace around it
    cc.add("trace", 12.0, 13.0)
    cc.add("lower", 10.0, 11.5)
    cc.add("compile", 20.0, 30.0)
    cc.add("cache_retrieval", 21.0, 22.0)  # inside the compile
    cc.count("cache_hits")
    tot = cc.totals()
    assert tot["trace"] == 3 and tot["lower"] == 1
    assert tot["trace_lower_s"] == 12.5  # [0, 11.5] and [12, 13]
    assert tot["compile_load_s"] == 10.0
    assert tot["compile"] == 1 and tot["cache_retrieval"] == 1
    assert tot["cache_hits"] == 1 and tot["cache_misses"] == 0


def test_merge_keeps_sorted_disjoint_intervals():
    ivs = []
    for s, e in [(5, 6), (0, 1), (2, 3), (0.5, 2.5), (8, 9), (4, 8.5)]:
        trace_lib._merge_into(ivs, s, e)
    assert ivs == [(0, 3), (4, 9)]


def test_fresh_reset_keeps_the_totals_of_its_epoch():
    rec = trace_lib.Recorder(enabled=True)
    assert rec.compile_at_reset is None
    rec._on_duration("/jax/core/compile/jaxpr_trace_duration", 0.25,
                     fun_name="f")
    rec._on_event("/jax/compilation_cache/cache_misses")
    rec._on_duration("/jax/some/other_duration", 9.0)
    span = rec.events[-1]
    assert span["name"] == "compile/trace" and span["cat"] == "compile"
    assert span["args"] == {"fun_name": "f"}
    assert trace_lib.validate_events(rec.events) == []
    rec.configure(fresh=True)
    at = dict(rec.compile_at_reset)
    assert at["trace"] == 1 and at["cache_misses"] == 1
    assert at["trace_lower_s"] == pytest.approx(0.25, abs=1e-3)
    rec._on_duration("/jax/core/compile/backend_compile_duration", 1.0)
    assert rec.compile_at_reset == at  # the epoch's totals stay put
    assert rec.compile.totals()["compile"] == 1  # lifetime counters grow
    assert [e["name"] for e in rec.events if e["ph"] == "X"] == [
        "compile/compile"
    ]


def test_a_disabled_recorder_counts_no_compile():
    rec = trace_lib.Recorder(enabled=False)
    rec._on_duration("/jax/core/compile/jaxpr_trace_duration", 0.25)
    rec._on_event("/jax/compilation_cache/cache_hits")
    assert rec.compile.totals()["trace"] == 0
    assert rec.compile.totals()["cache_hits"] == 0
    assert rec.events == []


def test_the_global_recorder_hears_jax_compile(monkeypatch):
    rec = trace_lib.RECORDER
    monkeypatch.setattr(rec, "enabled", True)
    trace_lib.listen_compile()
    trace_lib.listen_compile()  # once per process, however often asked
    before = rec.compile.totals()

    @jax.jit
    def fresh_fn(x):
        return x * 3 + 1

    fresh_fn(np.arange(5.0))
    after = rec.compile.totals()
    assert after["compile"] == before["compile"] + 1  # heard once
    for kind in ("trace", "lower"):
        assert after[kind] > before[kind], kind
    assert after["trace_lower_s"] > before["trace_lower_s"]
