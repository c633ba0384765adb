"""Fault registry, schedule compiler, and the faulted engine.

The central contracts:

* ZERO-COST-WHEN-OFF — ``faults=None``, ``faults=()``, and a benign
  never-firing event all reproduce the PR 5 golden engine bit-for-bit.
* Ground truth vs detection — a crashed server stops serving instantly
  but stays in the routed ring until the heartbeat timeout expires.
* Remap invalidation — after an epoch flip, no proxy (shared cache or
  fleet, any P) serves an owner-changed entry without revalidation.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FaultEvent, SimConfig, make_workload, simulate
from repro.core import cache as cache_lib
from repro.core import controllers as ctrl_lib
from repro.core import faults
from repro.core import fleet as fleet_lib

WL = make_workload("bursty", T=160, m=8, seed=3, N=512)
GOLDEN = "tests/data/control_golden.npz"


def _cfg(**kw):
    kw.setdefault("m", 8)
    kw.setdefault("N", 512)
    kw.setdefault("policy", "midas")
    return SimConfig(**kw)


# ---------------------------------------------------------------------------
# Registry + validation
# ---------------------------------------------------------------------------


def test_registry_lists_builtin_kinds():
    for kind in ("proxy_crash", "proxy_join", "server_brownout",
                 "gossip_partition", "ckpt_storm_fleet"):
        assert kind in faults.available()


def test_unknown_kind_lists_alternatives():
    with pytest.raises(ValueError, match="proxy_crash"):
        faults.get_class("power_cut")
    with pytest.raises(ValueError, match="available"):
        _cfg(faults=("power_cut",))


def test_config_validation_errors():
    with pytest.raises(ValueError, match="tuple"):
        _cfg(faults="proxy_crash")  # a bare string is a bug, not a list
    with pytest.raises(ValueError, match="target"):
        _cfg(faults=(FaultEvent("proxy_crash", target=8),))
    with pytest.raises(ValueError, match="magnitude"):
        _cfg(faults=(FaultEvent("server_brownout", magnitude=0.0),))
    with pytest.raises(ValueError, match="proxy"):
        _cfg(faults=(FaultEvent("gossip_partition", target=99),))
    with pytest.raises(ValueError, match="t0"):
        _cfg(faults=(FaultEvent("proxy_crash", t0=-5),))


def test_names_normalize_to_default_events():
    cfg = _cfg(faults=("server_brownout",))
    assert cfg.faults == (FaultEvent("server_brownout"),)
    assert cfg.fault_events == cfg.faults
    assert _cfg().fault_events == ()


def test_parse_fault_cli_specs():
    ev = faults.parse_fault("proxy_crash:t0=200,duration=300,target=2")
    assert ev == FaultEvent("proxy_crash", t0=200, duration=300, target=2)
    ev = faults.parse_fault("ckpt_storm_fleet:magnitude=0.25")
    assert ev.magnitude == 0.25
    with pytest.raises(ValueError, match="available"):
        faults.parse_fault("nope:t0=1")
    with pytest.raises(ValueError, match="parameter"):
        faults.parse_fault("proxy_crash:frequency=3")


# the fault program of the benchmark's checkpoint-storm deployment, as
# its configuration file spells it
CKPT_CRASH = (
    "ckpt_storm_fleet:t0=60,duration=120,magnitude=0.6",
    "proxy_crash:t0=80,duration=100,target=0",
)


@pytest.mark.parametrize(
    "spec,event",
    [
        (CKPT_CRASH[0], FaultEvent("ckpt_storm_fleet", t0=60, duration=120,
                                   magnitude=0.6)),
        (CKPT_CRASH[1], FaultEvent("proxy_crash", t0=80, duration=100,
                                   target=0)),
        ("server_brownout: target=3 ,magnitude=0.25",
         FaultEvent("server_brownout", target=3, magnitude=0.25)),
        ("proxy_join:", FaultEvent("proxy_join")),
        # a bare name keeps its meaning: the default event
        ("gossip_partition", FaultEvent("gossip_partition")),
    ],
    ids=["storm", "crash", "spaces", "no_parameters", "bare_name"],
)
def test_a_fault_string_is_the_event_built_directly(spec, event):
    a = _cfg(faults=(spec,))
    b = _cfg(faults=(event,))
    assert a.faults == (event,)
    assert a == b and hash(a) == hash(b)


@pytest.mark.parametrize(
    "spec,culprit",
    [
        ("proxy_crash:t0=80,frequency=3", "frequency=3"),
        ("proxy_crash:t0=eighty", "t0=eighty"),
        ("proxy_crash:magnitude=high", "magnitude=high"),
        ("proxy_crash:kind=proxy_join", "kind=proxy_join"),
        ("power_cut:t0=1", "power_cut"),
        ("power_cut", "power_cut"),
        ("proxy_crash:target=99", "target"),
    ],
    ids=["key", "int_value", "float_value", "kind_key", "kind",
         "bare_kind", "range"],
)
def test_a_bad_fault_string_is_refused_naming_it(spec, culprit):
    with pytest.raises(ValueError, match=culprit):
        _cfg(faults=(spec,))


def test_the_ckpt_crash_program_flips_twice_inside_the_horizon():
    fc = faults.compile_faults(_cfg(P=16, faults=CKPT_CRASH), 240)
    assert fc.epoch_masks.shape[0] == 3
    flips = np.flatnonzero(fc.epoch != fc.epoch_prev)
    # detected 10 ticks (500 ms) after the crash; rejoin at its end
    assert flips.tolist() == [90, 180]
    assert not fc.epoch_masks[1][0] and fc.epoch_masks[1][1:].all()
    assert fc.epoch_masks[0].all() and fc.epoch_masks[2].all()
    assert fc.has_storm and fc.has_remap and fc.has_downtime
    assert (fc.storm[60:180] == np.float32(0.6)).all()
    assert not fc.storm[:60].any() and not fc.storm[180:].any()


def test_all_dead_schedule_rejected():
    cfg = _cfg(m=2, faults=(
        FaultEvent("proxy_crash", t0=10, duration=50, target=0),
        FaultEvent("proxy_crash", t0=10, duration=50, target=1),
    ))
    with pytest.raises(ValueError, match="live"):
        simulate(cfg, WL, do_warmup=False)


# ---------------------------------------------------------------------------
# Compiler: detection, epochs, flags
# ---------------------------------------------------------------------------


def test_compile_none_for_empty():
    assert faults.compile_faults(_cfg(), 160) is None
    assert faults.compile_faults(_cfg(faults=()), 160) is None


def test_detection_lags_ground_truth():
    cfg = _cfg(faults=(FaultEvent("proxy_crash", t0=40, duration=60,
                                  target=0),))
    fc = faults.compile_faults(cfg, 160)
    K = fc.timeout_ticks
    assert K == faults.detect_ticks(cfg.dt_ms) == 10  # 500ms / 50ms
    assert not fc.member[40:100, 0].any()
    # presumed alive through the timeout, detected dead after it
    assert fc.detected[40:40 + K, 0].all()
    assert not fc.detected[40 + K:100, 0].any()
    # rejoin heartbeat makes re-detection immediate
    assert fc.detected[100:, 0].all()
    assert fc.has_downtime and fc.has_remap
    assert not (fc.has_brownout or fc.has_partition or fc.has_storm)
    # three epochs: all-live, server0-out, all-live again
    assert fc.epoch_masks.shape[0] == 3
    assert fc.owner_by_epoch is not None
    # epoch flips only where detection changed
    flip = fc.epoch != fc.epoch_prev
    assert flip.sum() == 2 and flip[50] and flip[100]


def test_benign_flags_all_off():
    cfg = _cfg(faults=(FaultEvent("server_brownout", t0=40, duration=60,
                                  target=1, magnitude=1.0),))
    fc = faults.compile_faults(cfg, 160)
    assert fc is not None
    assert not (fc.has_downtime or fc.has_remap or fc.has_brownout
                or fc.has_partition or fc.has_storm)


# ---------------------------------------------------------------------------
# Golden parity: the zero-fault engine is untouched
# ---------------------------------------------------------------------------


def test_zero_fault_paths_reproduce_golden():
    g = np.load(GOLDEN)
    want = g["midas_cache/queue_timeline"]
    for fa in (None, ()):
        cfg = _cfg(middleware=("cache",), faults=fa)
        r = simulate(cfg, WL, do_warmup=False)
        np.testing.assert_array_equal(r.queue_timeline, want)
        np.testing.assert_array_equal(r.d_timeline,
                                      g["midas_cache/d_timeline"])


def test_benign_event_value_equal_to_golden():
    """A never-firing event (brownout at magnitude 1.0) keeps every
    has_* flag off: the engine takes value-identical paths."""
    g = np.load(GOLDEN)
    cfg = _cfg(middleware=("cache",),
               faults=(FaultEvent("server_brownout", t0=40, duration=60,
                                  target=1, magnitude=1.0),))
    r = simulate(cfg, WL, do_warmup=False)
    np.testing.assert_array_equal(r.queue_timeline,
                                  g["midas_cache/queue_timeline"])
    np.testing.assert_array_equal(r.cache_hits,
                                  g["midas_cache/cache_hits"])


# ---------------------------------------------------------------------------
# Faulted engine behaviour
# ---------------------------------------------------------------------------

CRASH = (FaultEvent("proxy_crash", t0=40, duration=60, target=0),)


def test_crash_freezes_dead_server_and_recovers():
    cfg = _cfg(middleware=("cache",), faults=CRASH)
    r = simulate(cfg, WL, do_warmup=False)
    fc = faults.compile_faults(cfg, 160)
    q0 = r.queue_timeline[:, 0]
    K = fc.timeout_ticks
    # once detection lands, no new arrivals reach the dead server and
    # nothing drains: its queue is exactly frozen until rejoin
    frozen = q0[40 + K:100]
    assert (frozen == frozen[0]).all()
    assert (r.arrivals[40 + K:100, 0] == 0).all()
    # it serves again after rejoin and eventually drains
    assert r.arrivals[100:, 0].sum() > 0


def test_crash_scan_unroll_parity():
    cfg = _cfg(middleware=("cache",), faults=CRASH)
    r = simulate(cfg, WL, do_warmup=False)
    ru = simulate(dataclasses.replace(cfg, unroll_waves=True), WL,
                  do_warmup=False)
    np.testing.assert_array_equal(r.queue_timeline, ru.queue_timeline)
    np.testing.assert_array_equal(r.arrivals, ru.arrivals)
    np.testing.assert_array_equal(r.cache_hits, ru.cache_hits)


def test_brownout_slows_target_drain():
    cfg = _cfg(faults=(FaultEvent("server_brownout", t0=40, duration=80,
                                  target=1, magnitude=0.25),))
    r = simulate(cfg, WL, do_warmup=False)
    base = simulate(_cfg(), WL, do_warmup=False)
    win = slice(45, 120)
    assert (r.queue_timeline[win, 1].mean()
            > base.queue_timeline[win, 1].mean())


def test_storm_adds_write_arrivals():
    cfg = _cfg(middleware=("cache",),
               faults=(FaultEvent("ckpt_storm_fleet", t0=40, duration=40,
                                  magnitude=0.5),))
    r = simulate(cfg, WL, do_warmup=False)
    base = simulate(_cfg(middleware=("cache",)), WL, do_warmup=False)
    storm_win = r.arrivals[40:80].sum()
    assert storm_win > base.arrivals[40:80].sum()
    # outside the window the workload is untouched
    np.testing.assert_array_equal(r.arrivals[:40], base.arrivals[:40])


def test_partition_spikes_fleet_staleness():
    base = _cfg(middleware=("fleet_cache",), P=4, gossip_ms=100.0)
    cfg = dataclasses.replace(
        base,
        faults=(FaultEvent("gossip_partition", t0=20, duration=120,
                           target=1),),
    )
    r = simulate(cfg, WL, do_warmup=False)
    rb = simulate(base, WL, do_warmup=False)
    stale = np.asarray(r.final_cache.stale_p)
    stale_b = np.asarray(rb.final_cache.stale_p)
    # the partitioned proxy serves from an ever-staler snapshot
    assert stale[1] >= stale_b[1]
    assert stale.sum() >= stale_b.sum()


# ---------------------------------------------------------------------------
# Remap invalidation: the no-stale-owner property
# ---------------------------------------------------------------------------


def test_remap_invalidate_shared_cache():
    N = 64
    c = cache_lib.init_cache(N)
    c = c._replace(
        expiry_ms=cache_lib.to_table(jnp.full((N,), 1e9, jnp.float32), 0.0),
        cached_version=cache_lib.to_table(jnp.zeros((N,), jnp.int32), -1),
    )
    moved = jnp.arange(N) % 3 == 0
    c = cache_lib.remap_invalidate(c, moved)
    keys = jnp.arange(N, dtype=jnp.int32)
    ones = jnp.ones((N,), bool)
    _, hit = cache_lib.lookup_batch(
        c, keys, ones, ~ones, jnp.asarray(50.0)
    )
    hit = np.asarray(hit)
    assert not hit[np.asarray(moved)].any()
    assert hit[~np.asarray(moved)].all()


@pytest.mark.parametrize("P", [1, 2, 8])
def test_remap_invalidate_fleet_property(P):
    """No proxy — whatever lagged snapshot its gossip view selects —
    serves an owner-changed entry without revalidation."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    N, D = 32, 4

    @given(
        moved_bits=st.lists(st.booleans(), min_size=N, max_size=N),
        tick=st.integers(0, 10),
    )
    @settings(max_examples=20, deadline=None)
    def prop(moved_bits, tick):
        fs = fleet_lib.init_fleet(N, P, D)
        # every view (converged + all snapshots) holds live entries
        fs = fs._replace(
            shared=fs.shared._replace(
                expiry_ms=cache_lib.to_table(
                    jnp.full((N,), 1e9, jnp.float32), 0.0),
                cached_version=cache_lib.to_table(
                    jnp.zeros((N,), jnp.int32), -1),
            ),
            lag_expiry=cache_lib.to_table(
                jnp.full((D, N), 1e9, jnp.float32), 0.0),
            tick=jnp.asarray(tick, jnp.int32),
        )
        moved = jnp.asarray(moved_bits)
        fs = fleet_lib.remap_invalidate(fs, moved)
        keys = jnp.arange(N, dtype=jnp.int32)
        ones = jnp.ones((N,), bool)
        proxy = fleet_lib.proxy_assign(N, P, fs.tick)
        _, hit = fleet_lib.lookup_fleet(
            fs, keys, ones, ~ones, proxy, jnp.asarray(50.0),
            gossip_ms=100.0,
        )
        hit = np.asarray(hit)
        assert not hit[np.asarray(moved)].any()
        assert hit[~np.asarray(moved)].all()

    prop()


def test_faulted_run_serves_no_moved_entry():
    """End-to-end: with a crash mid-run, replaying each epoch's owner
    table shows cache hits never happen on a tick where the serving
    ring's owner differs from the installing ring's owner without a
    fresh install (spot check via total-hit accounting: hits under
    fault <= hits without fault, since invalidation only removes)."""
    cfg = _cfg(middleware=("cache",), faults=CRASH)
    base = _cfg(middleware=("cache",))
    r = simulate(cfg, WL, do_warmup=False)
    rb = simulate(base, WL, do_warmup=False)
    assert r.cache_hits.sum() <= rb.cache_hits.sum()


# ---------------------------------------------------------------------------
# Availability plumbing: install guard, Signals, controller reaction
# ---------------------------------------------------------------------------


def test_install_guard_under_degraded_avail():
    N = 16
    c = cache_lib.init_cache(N)
    keys = jnp.arange(N, dtype=jnp.int32)
    ones = jnp.ones((N,), bool)
    degraded = jnp.asarray(0.875, jnp.float32)
    c2, _ = cache_lib.lookup_batch(
        c, keys, ones, ~ones, jnp.asarray(10.0), avail=degraded
    )
    assert int(c2.bypasses) == N  # nothing installed while degraded
    assert (np.asarray(cache_lib.table_view(c2.expiry_ms, N)) == 0.0).all()
    c3, _ = cache_lib.lookup_batch(
        c, keys, ones, ~ones, jnp.asarray(10.0),
        avail=jnp.asarray(1.0, jnp.float32),
    )
    assert int(c3.bypasses) == 0  # full availability: installs proceed


def test_hysteresis_reacts_to_degraded_avail():
    cfg = _cfg()
    ctrl = ctrl_lib.get("hysteresis")
    st0 = ctrl.init(cfg, (0.15, 500.0))
    calm = ctrl_lib.make_signals(B=0.0, p99=0.0, rtt_ms=cfg.rtt_ms)
    # calm signals, full availability: no escalation
    st1, _ = ctrl.fast(st0, calm)
    assert int(st1.knobs.d) == int(st0.knobs.d)
    # calm signals, degraded availability: escalate immediately
    st2, _ = ctrl.fast(st0, calm._replace(avail=jnp.asarray(0.875)))
    assert int(st2.knobs.d) == int(st0.knobs.d) + 1


def test_no_fault_signal_ablation_blinds_controller():
    cfg = _cfg()
    ctrl = ctrl_lib.wrap_ablations(
        ctrl_lib.get("hysteresis"), "no_fault_signal"
    )
    st0 = ctrl.init(cfg, (0.15, 500.0))
    degraded = ctrl_lib.make_signals(
        B=0.0, p99=0.0, rtt_ms=cfg.rtt_ms, avail=0.875
    )
    st1, _ = ctrl.fast(st0, degraded)
    assert int(st1.knobs.d) == int(st0.knobs.d)  # flies blind


def test_unknown_ablation_still_rejected():
    with pytest.raises(ValueError, match="no_fault_signal"):
        ctrl_lib.parse_ablations("no_cache")


# ---------------------------------------------------------------------------
# Compound fault programs: overlap, sequence, cascade (PR 9)
# ---------------------------------------------------------------------------


def _compiled(events, T=160, **kw):
    return faults.compile_faults(_cfg(faults=tuple(events), **kw), T)


def test_overlap_requires_intersecting_windows():
    a = FaultEvent("proxy_crash", t0=20, duration=30, target=0)
    b = FaultEvent("ckpt_storm_fleet", t0=40, duration=40, magnitude=0.5)
    assert faults.overlap(a, b) == (a, b)
    c = FaultEvent("server_brownout", t0=100, duration=20, target=1,
                   magnitude=0.5)
    with pytest.raises(ValueError, match="sequence"):
        faults.overlap(a, c)


def test_program_schedule_is_elementwise_composition():
    """A compound program's compiled schedule equals the element-wise
    composition of its single-event schedules: membership ANDs, service
    scales multiply, partitions OR, storm intensities max, active ORs —
    the monotonic-apply property programs.py documents."""
    events = faults.overlap(
        FaultEvent("ckpt_storm_fleet", t0=30, duration=60, magnitude=0.5),
        FaultEvent("proxy_crash", t0=40, duration=40, target=0),
        FaultEvent("server_brownout", t0=35, duration=50, target=2,
                   magnitude=0.3),
        FaultEvent("gossip_partition", t0=30, duration=30, target=0),
    )
    prog = _compiled(events, P=4)
    singles = [_compiled((e,), P=4) for e in events]
    np.testing.assert_array_equal(
        prog.member, np.logical_and.reduce([s.member for s in singles]))
    np.testing.assert_allclose(
        prog.service_scale,
        np.prod([s.service_scale for s in singles], axis=0), rtol=1e-6)
    np.testing.assert_array_equal(
        prog.partition,
        np.logical_or.reduce([s.partition for s in singles]))
    np.testing.assert_allclose(
        prog.storm, np.max([s.storm for s in singles], axis=0))
    np.testing.assert_array_equal(
        prog.active, np.logical_or.reduce([s.active for s in singles]))


def test_sequence_retimes_and_composes():
    events = faults.rolling(
        "server_brownout", targets=(1, 2, 3), t0=20, duration=30,
        stagger=25, magnitude=0.3)
    assert [e.t0 for e in events] == [20, 45, 70]
    assert [e.target for e in events] == [1, 2, 3]
    prog = _compiled(events)
    singles = [_compiled((e,)) for e in events]
    np.testing.assert_allclose(
        prog.service_scale,
        np.prod([s.service_scale for s in singles], axis=0), rtol=1e-6)
    assert prog.has_brownout
    with pytest.raises(ValueError, match="stagger"):
        faults.sequence(events[0], t0=0, stagger=-1)


def test_cascade_fires_at_detection_time():
    """The cascade effect's resolved t0 is the trigger's *detection*
    tick (crash + heartbeat timeout) plus the offset — never earlier."""
    trig = FaultEvent("proxy_crash", t0=40, duration=60, target=0)
    casc = faults.CascadeEvent(
        trigger=trig,
        effect=FaultEvent("gossip_partition", t0=0, duration=30,
                          target=0),
        offset=5)
    cfg = _cfg(P=4, faults=(casc,))
    assert cfg.faults == (casc,)  # rides SimConfig next to plain events
    det = faults.detection_tick(trig, dt_ms=cfg.dt_ms, T=160, m=8, P=4)
    assert det == 40 + faults.detect_ticks(cfg.dt_ms)
    resolved = faults.resolve(
        (casc,), dt_ms=cfg.dt_ms, T=160, m=8, P=4)
    assert resolved[0] == trig
    assert resolved[1].t0 == det + 5
    assert resolved[1].t0 >= det
    fc = faults.compile_faults(cfg, 160)
    assert fc.partition[det + 5:det + 35, 0].all()
    assert not fc.partition[:det + 5].any()


def test_cascade_benign_trigger_detected_at_first_active_tick():
    trig = FaultEvent("server_brownout", t0=25, duration=40, target=1,
                      magnitude=0.3)
    assert faults.detection_tick(
        trig, dt_ms=50.0, T=160, m=8, P=1) == 25


def test_zero_length_program_reproduces_golden():
    """``sequence()`` is ``()`` — and both reproduce the golden engine
    bit-for-bit (zero-cost-when-off extends to empty programs)."""
    g = np.load(GOLDEN)
    assert faults.sequence() == ()
    cfg = _cfg(middleware=("cache",), faults=faults.sequence())
    r = simulate(cfg, WL, do_warmup=False)
    np.testing.assert_array_equal(r.queue_timeline,
                                  g["midas_cache/queue_timeline"])
    np.testing.assert_array_equal(r.d_timeline,
                                  g["midas_cache/d_timeline"])


def test_storm_from_pool_calibration():
    class _Pool:
        def backlogs(self):
            return [0, 30, 10, 0]

    ev = faults.storm_from_pool(_Pool(), t0=5, duration=9)
    assert ev.kind == "ckpt_storm_fleet"
    assert ev.t0 == 5 and ev.duration == 9
    assert ev.magnitude == pytest.approx(0.75)
