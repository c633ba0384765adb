"""The cache and fleet models on plain ``(N,)`` tables, for parity tests.

``repro.core.cache`` and ``repro.core.fleet`` store every per-key table
lane-tiled.  This module keeps the same semantics on logical ``(N,)``
arrays — sentinel ``N``, one-dimensional scatters, a ``(D, N)`` ring —
so the tests can require the tiled tables to read back bit for bit.
Counters and scalars are left to the tested state; only the tables and
the per-request flags are modelled here.
"""

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core import cache as cache_lib


class Tables(NamedTuple):
    expiry_ms: jnp.ndarray
    cached_version: jnp.ndarray
    global_version: jnp.ndarray
    last_write_ms: jnp.ndarray
    key_hazard: jnp.ndarray


class FleetTables(NamedTuple):
    shared: Tables
    last_event_ms: jnp.ndarray
    last_origin: jnp.ndarray
    lag_expiry: jnp.ndarray  # (D, N)
    lag_version: jnp.ndarray  # (D, N)


def init_tables(N: int) -> Tables:
    return Tables(
        expiry_ms=jnp.zeros((N,), jnp.float32),
        cached_version=jnp.full((N,), -1, jnp.int32),
        global_version=jnp.zeros((N,), jnp.int32),
        last_write_ms=jnp.full((N,), -1.0, jnp.float32),
        key_hazard=jnp.zeros((N,), jnp.float32),
    )


def init_fleet_tables(N: int, D: int) -> FleetTables:
    return FleetTables(
        shared=init_tables(N),
        last_event_ms=jnp.full((N,), -1e30, jnp.float32),
        last_origin=jnp.full((N,), -1, jnp.int32),
        lag_expiry=jnp.zeros((D, N), jnp.float32),
        lag_version=jnp.full((D, N), -1, jnp.int32),
    )


def apply(t: Tables, state: cache_lib.CacheState, keys, mask, is_write,
          hit, now_ms, mode, lease_ms=5000.0, rtt_ms=2.0,
          p_star=cache_lib.P_STAR):
    """One tick's table effects; ``state`` supplies the scalars (TTL,
    hazard, write-pressure window) the tiled model read in the same
    tick.  Returns the new tables and the (sentinel-``N``) event keys."""
    N = t.expiry_ms.shape[0]
    valid = mask & ~is_write
    w = is_write & mask
    wk = jnp.where(w, keys, N)
    wk_safe = jnp.minimum(wk, N - 1)
    gv = t.global_version.at[wk].add(1, mode="drop")
    key_hazard, last_write = t.key_hazard, t.last_write_ms
    if mode == "ttl_per_key":
        dt = jnp.maximum(now_ms - t.last_write_ms[wk_safe], 1.0)
        seen = t.last_write_ms[wk_safe] >= 0.0
        decayed = ((1.0 - cache_lib.BETA) * t.key_hazard[wk_safe]
                   + cache_lib.BETA / dt)
        upd = jnp.where(seen, decayed, 1.0 / jnp.maximum(dt, 1.0))
        key_hazard = t.key_hazard.at[wk].set(upd, mode="drop")
        last_write = t.last_write_ms.at[wk].set(now_ms, mode="drop")
    expiry = t.expiry_ms
    if mode == "lease":
        expiry = expiry.at[wk].set(0.0, mode="drop")
        inv_k = wk
    else:
        inv_k = jnp.full_like(wk, N)
    bypass = cache_lib.write_pressure(state) > cache_lib.W_HIGH
    mk = jnp.where(valid & ~hit & ~bypass, keys, N)
    mk_safe = jnp.minimum(mk, N - 1)
    if mode == "lease":
        ttl_k = jnp.full(keys.shape, lease_ms, jnp.float32)
    elif mode == "ttl_aggregate":
        ttl_k = jnp.full(keys.shape, 1.0, jnp.float32) * state.ttl_ms
    else:
        h = jnp.maximum(key_hazard[mk_safe], jnp.maximum(state.hazard, 1e-9))
        ttl_k = jnp.clip(-jnp.log1p(-p_star) / h, rtt_ms,
                         cache_lib.TTL_CAP_MS)
    expiry = expiry.at[mk].set(now_ms + ttl_k, mode="drop")
    cached_v = t.cached_version.at[mk].set(gv[mk_safe], mode="drop")
    new = Tables(expiry, cached_v, gv, last_write, key_hazard)
    return new, inv_k, mk


def lookup(t: Tables, state, keys, mask, is_write, now_ms, mode, **kw):
    """``cache_lib.lookup_batch`` on logical tables: (tables, hit)."""
    _, hit, _ = cache_lib.classify(
        t.expiry_ms[keys], t.cached_version[keys], t.global_version[keys],
        mask, is_write, now_ms)
    new, _, _ = apply(t, state, keys, mask, is_write, hit, now_ms, mode,
                      **kw)
    return new, hit


def lookup_fleet(f: FleetTables, state, keys, mask, is_write, proxy,
                 now_ms, mode, gossip_ms, **kw):
    """``fleet_lib.lookup_fleet`` on logical tables: (tables, hit)."""
    sh = f.shared
    slot = state.tick % f.lag_expiry.shape[0]
    fresh = (f.last_origin[keys] == proxy) | (
        now_ms - f.last_event_ms[keys] >= gossip_ms)
    _, hit, _ = cache_lib.classify(
        jnp.where(fresh, sh.expiry_ms[keys], f.lag_expiry[slot][keys]),
        jnp.where(fresh, sh.cached_version[keys], f.lag_version[slot][keys]),
        sh.global_version[keys], mask, is_write, now_ms)
    new_sh, inv, ins = apply(sh, state.shared, keys, mask, is_write, hit,
                             now_ms, mode, **kw)
    lev = f.last_event_ms.at[inv].set(now_ms, mode="drop")
    lor = f.last_origin.at[inv].set(proxy, mode="drop")
    return FleetTables(
        shared=new_sh,
        last_event_ms=lev.at[ins].set(now_ms, mode="drop"),
        last_origin=lor.at[ins].set(proxy, mode="drop"),
        lag_expiry=f.lag_expiry.at[slot].set(new_sh.expiry_ms),
        lag_version=f.lag_version.at[slot].set(new_sh.cached_version),
    ), hit


def remap(t: Tables, moved) -> Tables:
    return t._replace(expiry_ms=jnp.where(moved, 0.0, t.expiry_ms))


def assert_matches(tiled, fresh, logical, N: int) -> None:
    """Every table of ``tiled`` reads back as ``logical``, and its padding
    still holds what it held in ``fresh``, the state it started from."""
    def padding(a):
        return np.asarray(a).reshape(a.shape[:-2] + (-1,))[..., N:]

    for name, want in logical._asdict().items():
        got, init = getattr(tiled, name), getattr(fresh, name)
        if isinstance(want, Tables):
            assert_matches(got, init, want, N)
            continue
        np.testing.assert_array_equal(
            np.asarray(cache_lib.table_view(got, N)), np.asarray(want),
            err_msg=name)
        np.testing.assert_array_equal(padding(got), padding(init),
                                      err_msg=f"{name}: padding written")
