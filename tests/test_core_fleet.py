"""Proxy fleet: Δ=0 equivalence contract, gossip-delayed visibility,
the write-pressure install guard, the lane-tiled tables against the
logical ``(N,)`` model, and eager SimConfig validation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import logical_cache as logical
from repro.core import SimConfig, make_workload, simulate
from repro.core import cache as cache_lib
from repro.core import fleet as fleet_lib

DT = 50.0


def _one(key, write=False):
    return (jnp.asarray([key], jnp.int32), jnp.asarray([True]),
            jnp.asarray([bool(write)]))


def _step(fl, key, proxy, t, *, write=False, gossip_ms=100.0,
          mode="lease", lease_ms=100_000.0):
    """Drive one single-request tick at time t·DT served by ``proxy``."""
    keys, mask, w = _one(key, write)
    assert int(fl.tick) == t, "ticks must be driven in order"
    return fleet_lib.lookup_fleet(
        fl, keys, mask, w, jnp.asarray([proxy], jnp.int32),
        jnp.asarray(t * DT), mode=mode, lease_ms=lease_ms,
        gossip_ms=gossip_ms)


# ---------------------------------------------------------------------------
# Δ=0 equivalence (the fleet's core contract)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", cache_lib.MODES)
@pytest.mark.parametrize("P", [1, 2, 8])
def test_gossip_zero_fleet_matches_shared_table_in_sim(mode, P):
    """End-to-end: a gossip_ms=0 fleet run is bit-for-bit the shared-table
    cache run — counters AND queue dynamics."""
    wl = make_workload("skewed", T=150, m=4, seed=3, write_frac=0.2)
    a = simulate(SimConfig(m=4, policy="hash", middleware=("cache",),
                           cache_mode=mode), wl, do_warmup=False)
    b = simulate(SimConfig(m=4, P=P, policy="hash",
                           middleware=("fleet_cache",), cache_mode=mode,
                           gossip_ms=0.0), wl, do_warmup=False)
    sc, fc = a.final_cache, b.final_cache
    assert int(sc.hits) == int(fc.hits)
    assert int(sc.misses) == int(fc.misses)
    assert int(sc.stale_serves) == int(fc.stale_serves)
    assert int(sc.bypasses) == int(fc.bypasses)
    N = a.config.N
    for table in ("expiry_ms", "global_version"):
        np.testing.assert_array_equal(
            np.asarray(cache_lib.table_view(getattr(sc, table), N)),
            np.asarray(cache_lib.table_view(getattr(fc.shared, table), N)))
    np.testing.assert_array_equal(a.queue_timeline, b.queue_timeline)
    np.testing.assert_array_equal(a.cache_hits, b.cache_hits)


def test_per_proxy_counters_sum_to_aggregate():
    wl = make_workload("skewed", T=200, m=4, seed=5, write_frac=0.1)
    r = simulate(SimConfig(m=4, P=8, policy="hash",
                           middleware=("fleet_cache",), gossip_ms=100.0),
                 wl, do_warmup=False)
    fc = r.final_cache
    assert int(fc.hits_p.sum()) == int(fc.hits)
    assert int(fc.misses_p.sum()) == int(fc.misses)
    assert int(fc.stale_p.sum()) == int(fc.stale_serves)
    assert int(fc.bypasses_p.sum()) == int(fc.bypasses)
    # with the tick-rotated shard, no proxy monopolizes the traffic
    assert int((fc.hits_p + fc.misses_p > 0).sum()) == 8


# ---------------------------------------------------------------------------
# Gossip-delayed visibility (Δ > 0)
# ---------------------------------------------------------------------------


def test_remote_install_invisible_until_gossip_propagates():
    """gossip_ms=100 at dt=50: an entry installed by proxy 0 is invisible
    to proxy 1 for two ticks, then visible."""
    fl = fleet_lib.init_fleet(16, P=2, D=fleet_lib.delay_ticks(100.0, DT))
    fl, hit = _step(fl, 3, proxy=0, t=0)          # p0 installs (miss)
    assert not bool(hit[0])
    fl, hit = _step(fl, 3, proxy=1, t=1)          # too fresh for p1
    assert not bool(hit[0])
    fl, _ = _step(fl, 9, proxy=0, t=2)            # unrelated tick
    # p1's reinstall at t=1 is the latest event on key 3; by t=3 it is
    # 100 ms old, so every proxy sees the entry
    fl, hit = _step(fl, 3, proxy=0, t=3)
    assert bool(hit[0])
    assert int(fl.shared.hits) == 1 and int(fl.shared.misses) == 3


def test_own_events_always_visible_immediately():
    fl = fleet_lib.init_fleet(16, P=2, D=fleet_lib.delay_ticks(500.0, DT))
    fl, hit = _step(fl, 7, proxy=0, t=0, gossip_ms=500.0)
    assert not bool(hit[0])
    fl, hit = _step(fl, 7, proxy=0, t=1, gossip_ms=500.0)  # own install
    assert bool(hit[0])


def test_lease_mode_pays_stale_serves_under_gossip_delay():
    """The Δ=0 'staleness is zero by construction' claim breaks once
    invalidations take time to travel: a remote proxy serves the
    pre-write entry from its lagged view, and the omniscient counter
    records it."""
    fl = fleet_lib.init_fleet(16, P=2, D=fleet_lib.delay_ticks(100.0, DT))
    fl, _ = _step(fl, 3, proxy=0, t=0)                 # p0 installs
    fl, _ = _step(fl, 3, proxy=0, t=1, write=True)     # p0 invalidates
    fl, hit = _step(fl, 3, proxy=1, t=2)               # p1: lagged view
    assert bool(hit[0])                                # served locally...
    assert int(fl.shared.stale_serves) == 1            # ...and it was stale
    assert int(fl.stale_p[1]) == 1
    # once the invalidation propagates, the entry is gone fleet-wide
    fl, _ = _step(fl, 9, proxy=0, t=3)                 # unrelated tick
    fl, hit = _step(fl, 3, proxy=1, t=4)
    assert not bool(hit[0])


def test_gossip_delay_monotonically_hurts_lease_coherence():
    wl = make_workload("skewed", T=400, m=8, seed=2, write_frac=0.15)
    stale = []
    for g in (0.0, 100.0, 400.0):
        r = simulate(SimConfig(m=8, P=8, policy="hash",
                               middleware=("fleet_cache",), gossip_ms=g),
                     wl, do_warmup=False)
        stale.append(int(r.final_cache.stale_serves))
    assert stale[0] == 0                  # Δ=0 recovers the lease guarantee
    assert stale[2] > stale[1] >= stale[0]


# ---------------------------------------------------------------------------
# Lane-tiled tables: bit for bit the logical (N,) model
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("gossip_ms", [0.0, 100.0])
@pytest.mark.parametrize("P", [2, 8])
@pytest.mark.parametrize("N", [1000, 4097])
def test_tiled_fleet_matches_the_logical_model(N, P, gossip_ms):
    """Views, hits, the gossip log and the snapshot ring read the same
    as on plain (N,) tables and a (D, N) ring, the padding untouched."""
    rng = np.random.default_rng(N + P)
    D = fleet_lib.delay_ticks(gossip_ms, DT)
    fresh = fl = fleet_lib.init_fleet(N, P, D)
    f = logical.init_fleet_tables(N, D)
    R = 64
    for tick in range(30):
        hot = rng.integers(0, 16, R)
        spread = rng.integers(N - 256, N, R)
        keys = jnp.asarray(np.where(rng.random(R) < 0.5, hot, spread),
                           jnp.int32)
        mask = jnp.asarray(rng.random(R) < 0.9)
        w = jnp.asarray(rng.random(R) < 0.3)
        proxy = fleet_lib.proxy_assign(R, P, fl.tick)
        now = jnp.asarray(tick * DT, jnp.float32)
        f, want = logical.lookup_fleet(f, fl, keys, mask, w, proxy, now,
                                       "lease", gossip_ms)
        fl, hit = fleet_lib.lookup_fleet(fl, keys, mask, w, proxy, now,
                                         gossip_ms=gossip_ms)
        np.testing.assert_array_equal(np.asarray(hit), np.asarray(want))
    assert int(fl.hits) > 0
    logical.assert_matches(fl, fresh, f, N)


@pytest.mark.parametrize("N", [1000, 4097])
def test_fleet_remap_invalidate_drops_the_same_entries(N):
    """The padded moved mask drops the same entries from the converged
    table and from every ring slot, and no padding."""
    rng = np.random.default_rng(11)
    D = 3
    fresh = fleet_lib.init_fleet(N, 2, D)
    expiry = jnp.asarray(rng.uniform(1.0, 1e6, N), jnp.float32)
    lag = jnp.asarray(rng.uniform(1.0, 1e6, (D, N)), jnp.float32)
    fl = fresh._replace(
        shared=fresh.shared._replace(
            expiry_ms=cache_lib.to_table(expiry, 0.0)),
        lag_expiry=cache_lib.to_table(lag, 0.0),
    )
    f = logical.init_fleet_tables(N, D)
    f = f._replace(shared=f.shared._replace(expiry_ms=expiry),
                   lag_expiry=lag)
    moved = jnp.asarray(rng.random(N) < 0.3)
    f = f._replace(shared=logical.remap(f.shared, moved),
                   lag_expiry=jnp.where(moved, 0.0, f.lag_expiry))
    logical.assert_matches(fleet_lib.remap_invalidate(fl, moved), fresh, f,
                           N)



@pytest.mark.parametrize("N", [1000, 4097])
def test_remap_counted_counts_the_live_entries_the_remap_drops(N):
    """The converged table's count, and the fleet's over that table and
    every ring slot, are the moved entries whose expiry lies past the
    clock: the entries the remap turns from live to never-live.  The
    state it returns is :func:`remap_invalidate`'s."""
    rng = np.random.default_rng(13)
    D, now = 3, jnp.asarray(5e5, jnp.float32)
    fresh = fleet_lib.init_fleet(N, 2, D)
    expiry = rng.uniform(1.0, 1e6, N).astype(np.float32)
    lag = rng.uniform(1.0, 1e6, (D, N)).astype(np.float32)
    fl = fresh._replace(
        shared=fresh.shared._replace(
            expiry_ms=cache_lib.to_table(jnp.asarray(expiry), 0.0)),
        lag_expiry=cache_lib.to_table(jnp.asarray(lag), 0.0),
    )
    moved = jnp.asarray(rng.random(N) < 0.3)
    want = int((np.asarray(moved) & (expiry > now)).sum())
    c, got = cache_lib.remap_counted(fl.shared, moved, now)
    assert got.dtype == jnp.float32 and int(got) == want > 0
    jax.tree_util.tree_map(
        np.testing.assert_array_equal, c,
        cache_lib.remap_invalidate(fl.shared, moved),
    )
    want += int((np.asarray(moved) & (lag > now)).sum())
    after, got = fleet_lib.remap_counted(fl, moved, now)
    assert int(got) == want
    jax.tree_util.tree_map(
        np.testing.assert_array_equal, after,
        fleet_lib.remap_invalidate(fl, moved),
    )
    # nothing moved is live after the remap: a second count is 0
    assert int(fleet_lib.remap_counted(after, moved, now)[1]) == 0


# ---------------------------------------------------------------------------
# Write-pressure install guard (satellite: the E8 rename_storm fix)
# ---------------------------------------------------------------------------


def test_write_pressure_guard_flips_installs_off_and_back_on():
    c = cache_lib.init_cache(16)
    keys, mask, w = _one(5)
    # storm window: write mix far above W_HIGH with enough events
    c = c._replace(win_writes=jnp.asarray(100.0),
                   win_reads=jnp.asarray(10.0))
    assert float(cache_lib.write_pressure(c)) > cache_lib.W_HIGH
    c, hit = cache_lib.lookup_batch(c, keys, mask, w, jnp.asarray(0.0))
    assert not bool(hit[0])
    assert int(c.bypasses) == 1                      # install bypassed...
    c, hit = cache_lib.lookup_batch(c, keys, mask, w, jnp.asarray(1.0))
    assert not bool(hit[0]) and int(c.bypasses) == 2  # ...so still a miss
    # calm window: guard releases, installs resume
    c = c._replace(win_writes=jnp.asarray(0.0),
                   win_reads=jnp.asarray(100.0))
    assert float(cache_lib.write_pressure(c)) <= cache_lib.W_HIGH
    c, hit = cache_lib.lookup_batch(c, keys, mask, w, jnp.asarray(2.0))
    assert not bool(hit[0]) and int(c.bypasses) == 2
    c, hit = cache_lib.lookup_batch(c, keys, mask, w, jnp.asarray(3.0))
    assert bool(hit[0])                               # entry installed again


def test_write_pressure_guard_ignores_tiny_windows():
    """A couple of writes right after the window reset must not trip the
    guard — the live signal needs GUARD_MIN_EVENTS samples."""
    c = cache_lib.init_cache(16)
    c = c._replace(win_writes=jnp.asarray(3.0), win_reads=jnp.asarray(0.0))
    assert float(cache_lib.write_pressure(c)) <= cache_lib.W_HIGH


def test_guard_uses_slow_ewma_too():
    c = cache_lib.init_cache(16)
    c = c._replace(write_frac=jnp.asarray(0.5, jnp.float32))
    assert float(cache_lib.write_pressure(c)) > cache_lib.W_HIGH


# ---------------------------------------------------------------------------
# Eager SimConfig validation (satellite)
# ---------------------------------------------------------------------------


def test_unknown_policy_raises_at_construction():
    with pytest.raises(ValueError, match="available.*round_robin"):
        SimConfig(policy="no_such_policy")


def test_unknown_middleware_stage_raises_at_construction():
    with pytest.raises(ValueError, match="available.*fleet_cache"):
        SimConfig(middleware=("no_such_stage",))


def test_unknown_cache_mode_raises_at_construction():
    with pytest.raises(ValueError, match="available.*ttl_per_key"):
        SimConfig(cache_mode="write_through")


@pytest.mark.parametrize("field", ["m", "P", "N", "V", "n_groups"])
def test_nonpositive_sizes_raise_at_construction(field):
    with pytest.raises(ValueError, match=f"{field} must be a positive"):
        SimConfig(**{field: 0})


def test_negative_gossip_raises_at_construction():
    with pytest.raises(ValueError, match="gossip_ms"):
        SimConfig(gossip_ms=-1.0)


def test_valid_config_still_constructs():
    cfg = SimConfig(policy="midas", middleware=("fleet_cache",),
                    cache_mode="ttl_per_key", gossip_ms=250.0)
    assert cfg.middleware_chain == ("fleet_cache",)
