"""Hypothesis property tests on the system's invariants."""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")   # optional dep: skip cleanly when absent
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import cache as cache_lib
from repro.core import control as ctl
from repro.core import controllers as ctrl_lib
from repro.core import fleet as fleet_lib
from repro.core import hashring, telemetry

SETTINGS = dict(max_examples=30, deadline=None)


class _Cfg:
    """Minimal config stub for direct controller stepping."""

    rtt_ms = 2.0


@given(m=st.integers(2, 24), key_lo=st.integers(0, 10_000))
@settings(**SETTINGS)
def test_feasible_sets_always_valid(m, key_lo):
    ring = hashring.make_ring(m, V=32)
    keys = jnp.arange(key_lo, key_lo + 64, dtype=jnp.int32)
    feas = np.asarray(hashring.feasible_set(ring, keys, 4))
    prim = np.asarray(hashring.primary(ring, keys))
    assert ((feas >= 0) & (feas < m)).all()
    assert (feas[:, 0] == prim).all()
    # entries distinct whenever m >= 4
    if m >= 4:
        assert all(len(set(r.tolist())) == 4 for r in feas)


@given(pressures=st.lists(st.floats(0.0, 10.0), min_size=1, max_size=200))
@settings(**SETTINGS)
def test_control_knobs_always_bounded(pressures):
    """No pressure sequence can push knobs out of their paper bounds."""
    c = ctl.init_control(rtt_ms=2.0, b_tgt=0.0, p99_tgt=1.0)
    for p in pressures:
        # drive via imbalance directly (b_tgt=0 so B == pressure term)
        c = ctl.fast_update(c, jnp.asarray(p), jnp.asarray(0.0), 2.0,
                            jnp.asarray(0.0))
        assert ctl.D_MIN <= int(c.d) <= ctl.D_MAX
        assert ctl.DELTA_L_MIN <= float(c.delta_l) <= ctl.DELTA_L_MAX


import functools  # noqa: E402


@functools.lru_cache(maxsize=None)
def _traj_runner(ctrl_name, n_steps):
    """Jitted fast-loop trajectory: one compile per controller, every
    hypothesis example then runs as a single device call."""
    import jax

    c = ctrl_lib.get(ctrl_name)

    @jax.jit
    def run(state, B_seq):
        def body(s, B):
            s, k = c.fast(s, ctrl_lib.make_signals(
                B=B, p99=0.0, rtt_ms=2.0))
            return s, (k.d, k.delta_l, k.f_max)

        return jax.lax.scan(body, state, B_seq)

    return run


@given(ctrl_name=st.sampled_from(ctrl_lib.available()),
       pressures=st.lists(st.floats(0.0, 10.0), min_size=1, max_size=80),
       b_tgt=st.floats(0.0, 1.0))
@settings(**SETTINGS)
def test_every_registered_controller_keeps_knobs_in_spec_bounds(
        ctrl_name, pressures, b_tgt):
    """Registry-wide KnobSpec contract: NO registered controller, under
    ANY pressure sequence, may emit a knob outside its declared bounds
    (the engine's routing policies assume d ∈ {1..4}, f_max ≤ 1, ...)."""
    run = _traj_runner(ctrl_name, 80)
    # drive via imbalance directly (B − b_tgt is the pressure term);
    # pad to the runner's static length by holding the last value
    B = np.asarray(pressures + [pressures[-1]] * (80 - len(pressures)),
                   np.float32) + np.float32(b_tgt)
    s = ctrl_lib.get(ctrl_name).init(_Cfg, (b_tgt, 1.0))
    _, (d, dl, fm) = run(s, jnp.asarray(B))
    for name, vals in (("d", d), ("delta_l", dl), ("f_max", fm)):
        spec = ctrl_lib.spec(name)
        v = np.asarray(vals, np.float64)
        assert (spec.lo - 1e-6 <= v).all() and (v <= spec.hi + 1e-6).all(), \
            (ctrl_name, name, float(v.min()), float(v.max()))


@given(ctrl_name=st.sampled_from(ctrl_lib.available()),
       pressure=st.floats(0.0, 10.0))
@settings(**SETTINGS)
def test_every_registered_controller_is_oscillation_free_under_constant_load(
        ctrl_name, pressure):
    """No sustained limit cycle: under a CONSTANT signal a knob may ramp
    monotonically toward its fixed point (hysteresis steps, integrator
    ramps) but must never reverse direction — direction reversals under
    constant load ARE the oscillation the paper's hysteresis band
    exists to prevent.  (Whether a run also *settles* is a measured
    metric — E4's ``settled_frac`` — not a universal invariant: a slow
    integrator legitimately keeps ramping toward its fixed point.)"""
    n = 300
    run = _traj_runner(ctrl_name, n)
    s = ctrl_lib.get(ctrl_name).init(_Cfg, (0.0, 1.0))
    B = jnp.full((n,), pressure, jnp.float32)
    _, (d, dl, fm) = run(s, B)
    for name, vals in (("d", d), ("delta_l", dl), ("f_max", fm)):
        series = np.asarray(vals, np.float64)
        spec = ctrl_lib.spec(name)
        eps = 1e-9 * max(spec.hi - spec.lo, 1.0)
        diffs = np.diff(series)
        nz = diffs[np.abs(diffs) > eps]
        assert not ((nz > 0).any() and (nz < 0).any()), \
            (ctrl_name, name, "direction reversal under constant load")


@given(loads=st.lists(st.integers(0, 100), min_size=2, max_size=16),
       data=st.data())
@settings(**SETTINGS)
def test_lyapunov_steering_with_margin_2_strictly_decreases_v(loads, data):
    L = jnp.asarray(loads, jnp.float32)
    m = len(loads)
    p = data.draw(st.integers(0, m - 1))
    j = data.draw(st.integers(0, m - 1))
    if p == j:
        return
    if loads[p] - loads[j] >= 2:          # the admitted-steer condition
        dv = float(ctl.lyapunov_delta_v(L, jnp.asarray(p), jnp.asarray(j)))
        assert dv <= -2.0


@given(alpha=st.floats(0.01, 0.99),
       xs=st.lists(st.floats(-100, 100), min_size=1, max_size=50))
@settings(**SETTINGS)
def test_ewma_stays_within_input_hull(alpha, xs):
    lo, hi = min(xs + [0.0]), max(xs + [0.0])
    acc = jnp.asarray(0.0)
    for x in xs:
        acc = telemetry.ewma(acc, jnp.asarray(x), alpha)
        assert lo - 1e-4 <= float(acc) <= hi + 1e-4


@given(ops=st.lists(st.tuples(st.integers(0, 15), st.booleans()),
                    min_size=1, max_size=60))
@settings(**SETTINGS)
def test_lease_mode_never_serves_stale(ops):
    """In lease mode a cached read can never observe an outdated version."""
    c = cache_lib.init_cache(16)
    now = 0.0
    for key, is_write in ops:
        keys = jnp.asarray([key], jnp.int32)
        mask = jnp.asarray([True])
        w = jnp.asarray([is_write])
        c, _ = cache_lib.lookup_batch(c, keys, mask, w, jnp.asarray(now),
                                      mode="lease", lease_ms=500.0)
        now += 7.0
    assert int(c.stale_serves) == 0


@given(ops=st.lists(st.tuples(st.integers(0, 15), st.booleans()),
                    min_size=1, max_size=40),
       P=st.sampled_from([1, 2, 8]),
       mode=st.sampled_from(cache_lib.MODES))
@settings(**SETTINGS)
def test_fleet_gossip_zero_matches_shared_table(ops, P, mode):
    """Δ=0 equivalence contract: with instant gossip the fleet reproduces
    the converged shared-table cache bit-for-bit — same hit decisions,
    same counters, same table trajectory — for any P and coherence mode."""
    N = 16
    shared = cache_lib.init_cache(N)
    fl = fleet_lib.init_fleet(N, P, D=1)
    now = 0.0
    for t, (key, is_write) in enumerate(ops):
        keys = jnp.asarray([key], jnp.int32)
        mask = jnp.asarray([True])
        w = jnp.asarray([is_write])
        shared, hit_s = cache_lib.lookup_batch(
            shared, keys, mask, w, jnp.asarray(now), mode=mode,
            lease_ms=300.0)
        proxy = fleet_lib.proxy_assign(1, P, t)
        fl, hit_f = fleet_lib.lookup_fleet(
            fl, keys, mask, w, proxy, jnp.asarray(now), mode=mode,
            lease_ms=300.0, gossip_ms=0.0)
        assert bool(hit_s[0]) == bool(hit_f[0])
        now += 13.0
    for field in ("hits", "misses", "stale_serves", "bypasses"):
        assert int(getattr(shared, field)) == int(getattr(fl.shared, field))
    assert int(fl.hits_p.sum()) == int(shared.hits)
    for field in ("expiry_ms", "cached_version", "global_version",
                  "key_hazard"):
        np.testing.assert_array_equal(
            np.asarray(cache_lib.table_view(getattr(shared, field), N)),
            np.asarray(cache_lib.table_view(getattr(fl.shared, field), N)))


@given(writes=st.lists(st.floats(1.0, 1000.0), min_size=2, max_size=30))
@settings(**SETTINGS)
def test_ttl_never_exceeds_lease_or_cap(writes):
    c = cache_lib.init_cache(8)
    c = c._replace(win_writes=jnp.asarray(sum(writes)),
                   win_reads=jnp.asarray(100.0))
    lease = float(np.random.default_rng(0).uniform(1, 1e5))
    c2 = cache_lib.slow_update(c, 30_000.0, rtt_ms=1.0,
                               lease_remaining_ms=lease)
    assert float(c2.ttl_ms) <= min(lease, cache_lib.TTL_CAP_MS) + 1e-3
    assert float(c2.ttl_ms) >= 1.0


# ---------------------------------------------------------------------------
# Engine parity (DESIGN.md §9): scan-over-waves == unrolled reference
# ---------------------------------------------------------------------------

_PARITY_WL = None


def _parity_wl():
    global _PARITY_WL
    if _PARITY_WL is None:
        from repro.core import make_workload
        _PARITY_WL = make_workload("bursty", T=40, m=4, seed=9, N=128)
    return _PARITY_WL


@given(policy=st.sampled_from(("round_robin", "uniform", "power_of_d",
                               "midas", "jsq", "chbl")),
       mw=st.sampled_from(((), ("cache",), ("fleet_cache",))),
       n_groups=st.sampled_from((1, 3, 8)),
       fleet=st.booleans())
@settings(max_examples=12, deadline=None)
def test_wave_scan_parity_any_policy_middleware(policy, mw, n_groups,
                                                fleet):
    """Bit-for-bit: the wave scan equals the unrolled Python loop for any
    (policy, middleware chain, wave count, routing mode) draw."""
    import dataclasses

    from repro.core import SimConfig, simulate
    cfg = SimConfig(m=4, N=128, P=4, policy=policy, middleware=mw,
                    n_groups=n_groups, fleet_routing=fleet, gossip_ms=50.0)
    ref = dataclasses.replace(cfg, unroll_waves=True)
    wl = _parity_wl()
    a = simulate(cfg, wl, do_warmup=False)
    b = simulate(ref, wl, do_warmup=False)
    np.testing.assert_array_equal(a.queue_timeline, b.queue_timeline)
    np.testing.assert_array_equal(a.arrivals, b.arrivals)
    np.testing.assert_array_equal(a.steered, b.steered)
    np.testing.assert_array_equal(a.cache_hits, b.cache_hits)


# ---------------------------------------------------------------------------
# Observability: the windowing contract (DESIGN.md §13)
# ---------------------------------------------------------------------------


@given(
    xs=st.lists(
        st.floats(min_value=-1e6, max_value=1e6,
                  allow_nan=False, allow_infinity=False),
        min_size=0, max_size=120,
    ),
    hold=st.integers(2, 16),
)
@settings(max_examples=60, deadline=None)
def test_window_invariant_for_arbitrary_series(xs, hold):
    """0 <= begin <= end <= T for ANY finite timeline, and windowed
    statistics never produce non-finite parity shifts."""
    from repro.obs import windows

    w = windows.detect(np.asarray(xs), hold=hold)
    assert 0 <= w.begin <= w.end <= w.T == len(xs)
    stats = windows.windowed_stats(np.asarray(xs), w)
    assert np.isfinite(stats["shift"])


@given(level=st.floats(-100.0, 100.0), n=st.integers(20, 200))
@settings(max_examples=30, deadline=None)
def test_constant_load_always_opens_within_hold(level, n):
    """A constant-load trace has no transient: the stable window opens
    within the hold bound and runs to the horizon."""
    from repro.obs import windows

    w = windows.detect(np.full(n, level))
    assert w.method == "ewma_plateau" and w.begin <= windows.HOLD
    assert w.end == w.T == n
