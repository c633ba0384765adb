"""Cooperative metadata cache with leases / invalidations / adaptive TTLs.

Semantics (paper §IV-C):
  * only read-mostly ops (lookup/getattr/readdir) are cacheable;
  * an entry is served only within its validity horizon — lease expiry,
    explicit invalidation, or adaptive TTL; never past it;
  * coherence modes:
      - "lease"         — CephFS/HyCache+-style: writes invalidate proxy
                          entries immediately; entries otherwise live until
                          lease expiry.  Staleness is zero by construction.
      - "ttl_aggregate" — BeeGFS-style fallback: one hazard estimator for
                          the whole class, slow-loop tuned:
                              ĥ ← (1−β)·ĥ + β·rate      (β = 0.1)
                              TTL = −ln(1−p*)/ĥ
                          shrunk ×γ (=0.5) when write fraction > W_high,
                          floored at one RTT.
      - "ttl_per_key"   — the same hazard formula applied per key
                          (class = key): ĥ_k ← (1−β)ĥ_k + β/Δt_k at each
                          write of k, TTL_k set at install time.  This is
                          what restores P(stale) ≈ p* under zipf-skewed
                          write traffic, where the aggregate estimator
                          underestimates hot-key invalidation hazards.

Write-pressure guard: when the write-mix signal (slow-loop EWMA or the
live window once it has samples, see :func:`write_pressure`) exceeds
``W_HIGH``, the cache stops *installing* new entries (serve-through)
instead of merely shrinking TTLs — under mutation-dominated traffic
(rename storms) installs are invalidated before they can be reused, so
caching only adds staleness risk and churn.  Bypassed installs are
counted in ``CacheState.bypasses``.

Layout: every per-key table is stored lane-tiled, shape
``(N_pad // 128, 128)`` with ``N_pad`` = ``N`` rounded up to a multiple
of 1024, so each grid cell's table is whole ``(8, 128)`` tiles — the
layout the TPU's scatter works on in place.  An ``(N,)`` table batched
under the sweep's vmap is not: every scatter into it copies the whole
batch into a linear buffer and back.  Key ``k`` lives at
:func:`key_index` ``(k >> 7, k & 127)``; the padding is never written
and never read.  Readers outside this module and :mod:`repro.core.fleet`
see the logical ``(N,)`` table through :func:`table_view`.

This module holds the *converged shared table*: the state every proxy
agrees on once gossip has propagated (the paper's space bound is
O(m + C) per-namespace-key).  ``lookup_batch`` processes a tick against
that table directly — the Δ=0 gossip limit.  The multi-proxy view, where
announcements and invalidations take ``gossip_ms`` to travel, lives in
:mod:`repro.core.fleet`, which reuses :func:`classify` /
:func:`apply_batch` so the two models are bit-for-bit identical at Δ=0.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.faults.base import AVAIL_FULL

BETA = 0.1
GAMMA = 0.5
W_HIGH = 0.3
P_STAR = 1e-4
TTL_CAP_MS = 60_000.0
GUARD_MIN_EVENTS = 64.0
MODES = ("lease", "ttl_aggregate", "ttl_per_key")


_LANE_BITS = 7
LANES = 1 << _LANE_BITS  # 128
_TILE_KEYS = 8 * LANES  # one (8, 128) tile of 32-bit words


def table_shape(N: int) -> Tuple[int, int]:
    """Lane-tiled shape ``(N_pad // 128, 128)`` of an (N,) per-key table."""
    n_pad = -(-N // _TILE_KEYS) * _TILE_KEYS
    return n_pad // LANES, LANES


def to_table(x: jnp.ndarray, fill) -> jnp.ndarray:
    """Tile a logical ``(..., N)`` array; the padding holds ``fill``."""
    N = x.shape[-1]
    rows, lanes = table_shape(N)
    pad = [(0, 0)] * (x.ndim - 1) + [(0, rows * lanes - N)]
    x = jnp.pad(x, pad, constant_values=fill)
    return x.reshape(x.shape[:-1] + (rows, lanes))


def table_view(table: jnp.ndarray, N: int) -> jnp.ndarray:
    """The logical ``(..., N)`` view of a lane-tiled table (or ring)."""
    return table.reshape(table.shape[:-2] + (-1,))[..., :N]


def key_index(keys: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(row, lane)`` of each key in a lane-tiled table."""
    return keys >> _LANE_BITS, keys & (LANES - 1)


class CacheState(NamedTuple):
    # per-key tables, lane-tiled (table_shape(N)); read via table_view
    expiry_ms: jnp.ndarray       # float32 absolute expiry time
    cached_version: jnp.ndarray  # int32 version stored at insert
    global_version: jnp.ndarray  # int32 authoritative version
    last_write_ms: jnp.ndarray   # float32 last write time per key
    key_hazard: jnp.ndarray      # float32 per-key ĥ (1/ms)
    ttl_ms: jnp.ndarray          # () float32 aggregate adaptive TTL
    hazard: jnp.ndarray          # () float32 aggregate ĥ
    write_frac: jnp.ndarray      # () float32 EWMA of write mix W_c
    win_writes: jnp.ndarray      # () float32 slow-window writes
    win_reads: jnp.ndarray       # () float32 slow-window reads
    hits: jnp.ndarray            # () int32
    misses: jnp.ndarray          # () int32
    stale_serves: jnp.ndarray    # () int32
    bypasses: jnp.ndarray        # () int32 installs skipped by the guard


def init_cache(N: int, ttl_init_ms: float = 100.0) -> CacheState:
    z32 = jnp.zeros((), jnp.int32)
    zf = jnp.zeros((), jnp.float32)
    shape = table_shape(N)
    return CacheState(
        expiry_ms=jnp.zeros(shape, jnp.float32),
        cached_version=jnp.full(shape, -1, jnp.int32),
        global_version=jnp.zeros(shape, jnp.int32),
        last_write_ms=jnp.full(shape, -1.0, jnp.float32),
        key_hazard=jnp.zeros(shape, jnp.float32),
        ttl_ms=jnp.asarray(ttl_init_ms, jnp.float32),
        hazard=jnp.asarray(1e-6, jnp.float32),
        write_frac=zf,
        win_writes=zf,
        win_reads=zf,
        hits=z32,
        misses=z32,
        stale_serves=z32,
        bypasses=z32,
    )


def write_pressure(cache: CacheState) -> jnp.ndarray:
    """Write-mix signal the install guard compares against ``W_HIGH``.

    The slow-loop EWMA (β=0.1 per T_slow window) carries hysteresis
    across windows but needs minutes to cross W_HIGH; a rename storm is
    over before it reacts.  So the guard also listens to the *live*
    window's mix once it holds enough events to be meaningful —
    whichever signal is higher wins.
    """
    n = cache.win_writes + cache.win_reads
    wf_window = cache.win_writes / jnp.maximum(n, 1.0)
    live = jnp.where(n >= GUARD_MIN_EVENTS, wf_window, 0.0)
    return jnp.maximum(cache.write_frac, live)


class BatchEffects(NamedTuple):
    """Per-request effect vectors of one ``apply_batch`` tick — the
    single source both models derive counters and gossip events from."""

    inv_keys: jnp.ndarray  # (R,) invalidation-event keys (sentinel N_pad)
    ins_keys: jnp.ndarray  # (R,) install-event keys (sentinel N_pad)
    miss: jnp.ndarray      # (R,) bool valid read misses
    bypassed: jnp.ndarray  # (R,) bool misses the guard served through


def classify(
    expiry_view: jnp.ndarray,
    version_view: jnp.ndarray,
    gv_view: jnp.ndarray,
    mask: jnp.ndarray,
    is_write: jnp.ndarray,
    now_ms: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Classify one tick's requests against a *view* of the table.

    ``expiry_view`` / ``version_view`` are the per-request (R,) entry
    fields as the serving proxy sees them (the converged table in the
    shared model; possibly gossip-lagged in the fleet model).  ``gv_view``
    is the authoritative version — staleness is an omniscient metric, so
    it is never lagged.  Returns ``(valid, hit, stale)`` bool vectors.
    """
    valid = mask & ~is_write
    live = (expiry_view > now_ms) & (version_view >= 0)
    hit = valid & live
    stale = hit & (version_view < gv_view)
    return valid, hit, stale


def apply_batch(
    cache: CacheState,
    keys: jnp.ndarray,
    mask: jnp.ndarray,
    is_write: jnp.ndarray,
    hit: jnp.ndarray,
    stale: jnp.ndarray,
    now_ms: jnp.ndarray,
    *,
    mode: str = "lease",
    lease_ms: float = 5000.0,
    rtt_ms: float = 2.0,
    p_star: float = P_STAR,
    avail: Optional[jnp.ndarray] = None,
) -> Tuple[CacheState, BatchEffects]:
    """Apply one tick's effects to the converged table, given hit flags.

    Writes always reach the server: they bump the authoritative version,
    feed the hazard estimators and, in lease mode, invalidate the entry.
    Misses install an entry with the mode's validity horizon — unless the
    write-pressure guard is active, in which case installs are bypassed
    and counted.  ``avail`` (optional () float32, the detected live
    fraction from the fault layer) extends the guard: while membership
    is degraded (``avail < AVAIL_FULL``) installs are bypassed too —
    entries installed against a shrunken ring would be invalidated
    wholesale at the next remap epoch, so installing only adds churn.

    Returns ``(new_cache, effects)``: the event-key vectors in
    ``effects`` (sentinel ``N_pad`` where no event) are the gossip
    payload the fleet model propagates between proxies, and its flag
    vectors are what per-proxy counters must be derived from so they
    always sum to the aggregate counters updated here.
    """
    assert mode in MODES, mode
    # "no event" sentinel: N_pad, the first key past the padding, whose
    # row lies past the table.  It must be out of bounds: mode="drop"
    # only drops genuinely out-of-bounds scatters.  Gathers at it are
    # clamped into the padding and their values only feed dropped writes.
    none = cache.expiry_ms.size
    valid = mask & ~is_write

    # --- writes: version bump + hazard update (+ lease invalidation) -----
    w = is_write & mask
    wk = jnp.where(w, keys, none)
    wi = key_index(wk)
    wi_safe = key_index(jnp.minimum(wk, none - 1))
    gv = cache.global_version.at[wi].add(1, mode="drop")
    if mode == "ttl_per_key":
        dt = jnp.maximum(now_ms - cache.last_write_ms[wi_safe], 1.0)
        seen = cache.last_write_ms[wi_safe] >= 0.0
        decayed = (1.0 - BETA) * cache.key_hazard[wi_safe] + BETA / dt
        upd = jnp.where(seen, decayed, 1.0 / jnp.maximum(dt, 1.0))
        key_hazard = cache.key_hazard.at[wi].set(upd, mode="drop")
        last_write = cache.last_write_ms.at[wi].set(now_ms, mode="drop")
    else:
        # the per-key hazard log feeds only the ttl_per_key horizon;
        # lease / ttl_aggregate leave both tables untouched — two
        # fewer full-table scatters on every tick of the hot path
        key_hazard = cache.key_hazard
        last_write = cache.last_write_ms
    expiry = cache.expiry_ms
    if mode == "lease":
        # immediate invalidation at the (converged) proxy table
        expiry = expiry.at[wi].set(0.0, mode="drop")
        inv_k = wk
    else:
        inv_k = jnp.full_like(wk, none)  # TTL modes: expiry-only, no events

    # --- misses install the entry with the mode's validity horizon -------
    # ... unless the write-pressure guard trips: serve-through, no install
    miss = valid & ~hit
    bypass = write_pressure(cache) > W_HIGH
    if avail is not None:
        bypass = bypass | (avail < AVAIL_FULL)
    install = miss & ~bypass
    mk = jnp.where(install, keys, none)
    mi = key_index(mk)
    mi_safe = key_index(jnp.minimum(mk, none - 1))
    if mode == "lease":
        ttl_k = jnp.full(keys.shape, lease_ms, jnp.float32)
    elif mode == "ttl_aggregate":
        ttl_k = jnp.full(keys.shape, 1.0, jnp.float32) * cache.ttl_ms
    else:  # ttl_per_key
        # hierarchical: per-key hazard when observed, class hazard as the
        # conservative prior for keys with no write history yet ("TTLs
        # err on freshness", §IV-C).
        h = jnp.maximum(key_hazard[mi_safe], jnp.maximum(cache.hazard, 1e-9))
        ttl_k = -jnp.log1p(-p_star) / h
        ttl_k = jnp.clip(ttl_k, rtt_ms, TTL_CAP_MS)
    expiry = expiry.at[mi].set(now_ms + ttl_k, mode="drop")
    cached_v = cache.cached_version.at[mi].set(gv[mi_safe], mode="drop")

    new = cache._replace(
        expiry_ms=expiry,
        cached_version=cached_v,
        global_version=gv,
        last_write_ms=last_write,
        key_hazard=key_hazard,
        win_writes=cache.win_writes + jnp.sum(w),
        win_reads=cache.win_reads + jnp.sum(valid),
        hits=cache.hits + jnp.sum(hit).astype(jnp.int32),
        misses=cache.misses + jnp.sum(miss).astype(jnp.int32),
        stale_serves=cache.stale_serves + jnp.sum(stale).astype(jnp.int32),
        bypasses=cache.bypasses + jnp.sum(miss & bypass).astype(jnp.int32),
    )
    eff = BatchEffects(
        inv_keys=inv_k, ins_keys=mk, miss=miss, bypassed=miss & bypass
    )
    return new, eff


def lookup_batch(
    cache: CacheState,
    keys: jnp.ndarray,
    mask: jnp.ndarray,
    is_write: jnp.ndarray,
    now_ms: jnp.ndarray,
    *,
    mode: str = "lease",
    lease_ms: float = 5000.0,
    rtt_ms: float = 2.0,
    p_star: float = P_STAR,
    avail: Optional[jnp.ndarray] = None,
) -> Tuple[CacheState, jnp.ndarray]:
    """Process one tick of requests against the converged shared table.

    Reads hitting a valid entry are served at the proxy (no server load).
    Writes always reach the server, bump the authoritative version and,
    in lease mode, invalidate the proxy entry.  ``avail`` feeds the
    availability install guard (see :func:`apply_batch`).  Returns
    (new_cache, served_locally: (R,) bool).
    """
    assert mode in MODES, mode
    ki = key_index(keys)
    _, hit, stale = classify(
        cache.expiry_ms[ki],
        cache.cached_version[ki],
        cache.global_version[ki],
        mask,
        is_write,
        now_ms,
    )
    new, _ = apply_batch(
        cache,
        keys,
        mask,
        is_write,
        hit,
        stale,
        now_ms,
        mode=mode,
        lease_ms=lease_ms,
        rtt_ms=rtt_ms,
        p_star=p_star,
        avail=avail,
    )
    return new, hit


def remap_invalidate(
    cache: CacheState, moved: jnp.ndarray
) -> CacheState:
    """Drop every entry whose ring owner just changed (``moved``: (N,)
    bool from the fault layer's per-epoch owner diff).

    Placement shift makes a cached entry unverifiable — the proxy's
    lease/TTL was granted by a server that no longer owns the key — so
    expiry is zeroed (never-live) and the next read revalidates at the
    new owner.  Entries whose owner did not move are untouched
    (consistent-hashing minimal disruption carries over to the cache).
    The padding of the tiled table is never moved.
    """
    return cache._replace(
        expiry_ms=jnp.where(to_table(moved, False), 0.0, cache.expiry_ms)
    )


def remap_counted(
    cache: CacheState, moved: jnp.ndarray, now_ms: jnp.ndarray
) -> Tuple[CacheState, jnp.ndarray]:
    """:func:`remap_invalidate`, and the () float32 count of the entries
    it drops that were still live at ``now_ms``."""
    live = to_table(moved, False) & (cache.expiry_ms > now_ms)
    n = jnp.sum(live).astype(jnp.float32)
    # the count reads the table before the remap rewrites it; the
    # barrier orders it first, so the remap still writes in place
    n, expiry = jax.lax.optimization_barrier((n, cache.expiry_ms))
    return remap_invalidate(cache._replace(expiry_ms=expiry), moved), n


def slow_update(
    cache: CacheState,
    window_ms: float,
    rtt_ms: float,
    lease_remaining_ms: float = jnp.inf,
    p_star: float = P_STAR,
    ttl_scale=1.0,
) -> CacheState:
    """T_slow retune of the aggregate TTL from the hazard estimator.

    ``ttl_scale`` is the controller-emitted TTL multiplier
    (``Knobs.ttl_scale``, bounds in ``controllers.KNOB_SPECS``): the
    hazard estimator owns the horizon, the control plane scales it —
    applied before the transport floor/cap so a shrinking controller
    can never push a TTL below one RTT.  The default (1.0) is exact
    identity.
    """
    # the padding holds version -1, so it never counts as cached
    n_cached = jnp.maximum(jnp.sum(cache.cached_version >= 0), 1)
    rate = cache.win_writes / n_cached / window_ms  # invalidations/entry/ms
    hazard = (1.0 - BETA) * cache.hazard + BETA * rate
    hazard = jnp.maximum(hazard, 1e-9)
    ttl = -jnp.log1p(-p_star) / hazard
    ttl = jnp.minimum(ttl, lease_remaining_ms)
    n_events = jnp.maximum(cache.win_writes + cache.win_reads, 1.0)
    wf = cache.win_writes / n_events
    write_frac = (1.0 - BETA) * cache.write_frac + BETA * wf
    ttl = jnp.where(write_frac > W_HIGH, ttl * GAMMA, ttl)
    ttl = ttl * ttl_scale  # controller slow-loop retune (Knobs.ttl_scale)
    ttl = jnp.clip(ttl, rtt_ms, TTL_CAP_MS)  # transport floor: >= one RTT
    zf = jnp.zeros((), jnp.float32)
    return cache._replace(
        ttl_ms=ttl,
        hazard=hazard,
        write_frac=write_frac,
        win_writes=zf,
        win_reads=zf,
    )
