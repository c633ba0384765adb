"""Proxy fleet: P real proxies with gossip-delayed cache coherence.

The shared-table model in :mod:`repro.core.cache` is the Δ=0 gossip limit
of the paper's cooperative cache — every entry announcement and write
invalidation is instantly visible to all P proxies, so one converged
table suffices.  This module drops that assumption: requests are sharded
across ``P`` proxies per tick, and each proxy serves from *its own view*
of the table, where remote events (installs and invalidations gossiped by
other proxies, §IV-C) only become visible ``gossip_ms`` after they
happen.

Representation.  Rather than materializing P physical tables (O(P·N)
state whose Δ=0 merge would have to reproduce the shared scatter order
exactly), the fleet keeps

  * ``shared``      — the converged table, updated every tick by exactly
                      the shared model's
                      :func:`repro.core.cache.apply_batch` (so the
                      eventual state *is* the shared model's state);
  * ``last_event_ms`` / ``last_origin``
                    — per-key gossip log: when the most recent install
                      or invalidation happened, and which proxy
                      originated it;
  * ``lag_expiry`` / ``lag_version``
                    — a ring buffer of D converged-table snapshots,
                      D = ceil(gossip_ms / dt_ms) ticks deep.

Every per-key table (and each ring slot) is lane-tiled like the
converged table's (:func:`repro.core.cache.table_shape`) and indexed
through :func:`repro.core.cache.key_index`.

Proxy p's view of key k is the *fresh* converged entry iff p originated
the last event on k or that event is at least ``gossip_ms`` old;
otherwise p sees the *lagged* snapshot from D ticks ago — i.e. the table
as it was before any not-yet-propagated event.  With
D = ceil(gossip_ms/dt_ms) the two visibility tests agree exactly: an
event from tick t − j is time-visible (age j·dt ≥ gossip_ms) iff j ≥ D,
which is precisely when it is contained in the snapshot.  Multiple
events on one key inside the gossip window collapse to last-event-wins —
a documented approximation (a proxy can lose sight of its own install if
another proxy re-announced the key meanwhile); interleavings finer than
dt are not modeled.

Equivalence contract (tested property): at ``gossip_ms=0`` every event
is immediately visible, each view equals the converged table, and the
fleet reproduces the shared-table model bit-for-bit — same
hit/miss/stale/bypass counters and same table trajectory — for any P,
across all coherence modes.  Staleness is accounted omnisciently against
the authoritative ``global_version`` (the server's), which gossip never
lags: with Δ>0 a proxy can serve an entry another proxy's write already
invalidated, and that is exactly the stale-serve rate E9 measures.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import cache as cache_lib


class FleetState(NamedTuple):
    """Carried scan state of the proxy fleet (one pytree)."""

    shared: cache_lib.CacheState  # converged table + aggregate counters
    tick: jnp.ndarray             # () int32 fleet-local tick counter
    # per-key tables lane-tiled (cache_lib.table_shape(N)), rings (D, *that)
    last_event_ms: jnp.ndarray    # float32 time of last gossip event
    last_origin: jnp.ndarray      # int32 proxy that originated it
    lag_expiry: jnp.ndarray       # (D, ...) float32 snapshot ring buffer
    lag_version: jnp.ndarray      # (D, ...) int32 snapshot ring buffer
    hits_p: jnp.ndarray           # (P,) int32 per-proxy hits
    misses_p: jnp.ndarray         # (P,) int32 per-proxy misses
    stale_p: jnp.ndarray          # (P,) int32 per-proxy stale serves
    bypasses_p: jnp.ndarray       # (P,) int32 per-proxy guard bypasses

    # Aggregate counters mirror the shared-table model bit-for-bit; the
    # per-proxy vectors expose the divergence the shared model hides.
    @property
    def hits(self) -> jnp.ndarray:
        return self.shared.hits

    @property
    def misses(self) -> jnp.ndarray:
        return self.shared.misses

    @property
    def stale_serves(self) -> jnp.ndarray:
        return self.shared.stale_serves

    @property
    def bypasses(self) -> jnp.ndarray:
        return self.shared.bypasses


def delay_ticks(gossip_ms: float, dt_ms: float) -> int:
    """Gossip delay in whole ticks; the ring buffer depth (static, >=1)."""
    if gossip_ms < 0:
        raise ValueError(f"gossip_ms must be >= 0, got {gossip_ms}")
    return max(int(math.ceil(gossip_ms / dt_ms)), 1)


def proxy_assign(
    R: int, P: int, tick: Union[jnp.ndarray, int] = 0
) -> jnp.ndarray:
    """Shard request slots across proxies: slot r → proxy (r + tick) % P.

    Workload grids fill slots as a masked prefix, so the modulo spreads
    each tick's live requests across the fleet, and the tick rotation
    decorrelates slot rank from proxy over time — the paper's
    client-pinned proxies with no key affinity.  At Δ=0 the assignment
    is immaterial to cache results (every proxy shares one view), so the
    equivalence contract does not depend on this choice.
    """
    tick = jnp.asarray(tick, jnp.int32)
    return ((jnp.arange(R, dtype=jnp.int32) + tick) % P).astype(jnp.int32)


def wave_views(L_hat_p: jnp.ndarray, tick: jnp.ndarray) -> jnp.ndarray:
    """(P, m) telemetry views reordered so row g is the view of the
    proxy serving routing wave g this tick — proxy (g + tick) % P, the
    same rotation as :func:`proxy_assign`.  One gather up front lets the
    engine feed per-wave views to its wave scan instead of issuing P
    dynamic row reads (bit-for-bit the same rows)."""
    P = L_hat_p.shape[0]
    idx = (jnp.arange(P, dtype=jnp.int32) + jnp.asarray(tick, jnp.int32)) % P
    return L_hat_p[idx]


def init_fleet(
    N: int, P: int, D: int, ttl_init_ms: float = 100.0
) -> FleetState:
    if P <= 0:
        raise ValueError(f"fleet needs P >= 1 proxies, got {P}")
    if D <= 0:
        raise ValueError(f"fleet needs D >= 1 ring-buffer slots, got {D}")
    zp = jnp.zeros((P,), jnp.int32)
    shape = cache_lib.table_shape(N)
    return FleetState(
        shared=cache_lib.init_cache(N, ttl_init_ms),
        tick=jnp.zeros((), jnp.int32),
        # -inf-like sentinel: "no event yet" is always propagation-old
        last_event_ms=jnp.full(shape, -1e30, jnp.float32),
        last_origin=jnp.full(shape, -1, jnp.int32),
        # empty-cache snapshots: expiry 0 / version -1 == never live
        lag_expiry=jnp.zeros((D,) + shape, jnp.float32),
        lag_version=jnp.full((D,) + shape, -1, jnp.int32),
        hits_p=zp,
        misses_p=zp,
        stale_p=zp,
        bypasses_p=zp,
    )


def lookup_fleet(
    state: FleetState,
    keys: jnp.ndarray,
    mask: jnp.ndarray,
    is_write: jnp.ndarray,
    proxy: jnp.ndarray,
    now_ms: jnp.ndarray,
    *,
    mode: str = "lease",
    lease_ms: float = 5000.0,
    rtt_ms: float = 2.0,
    p_star: float = cache_lib.P_STAR,
    gossip_ms: float = 0.0,
    partitioned: Optional[jnp.ndarray] = None,
    avail: Optional[jnp.ndarray] = None,
) -> Tuple[FleetState, jnp.ndarray]:
    """Process one tick of requests, each served by its assigned proxy.

    ``proxy`` maps every request slot to the proxy serving it (see
    :func:`proxy_assign`).  Hits are decided against the serving proxy's
    gossip view; effects land on the converged table via the shared
    model's ``apply_batch``, then this tick's install/invalidation
    events enter the gossip log and the snapshot ring buffer.

    ``partitioned`` (optional (P,) bool from the fault layer) cuts a
    proxy off from gossip: remote events never become time-visible to
    it while partitioned — it keeps serving from the lagged snapshot
    (plus its own events), which is exactly the staleness spike a
    gossip partition causes.  ``avail`` feeds the availability install
    guard (see :func:`repro.core.cache.apply_batch`).  Returns
    ``(new_state, served_locally: (R,) bool)``.
    """
    sh = state.shared
    P = state.hits_p.shape[0]
    D = state.lag_expiry.shape[0]

    # --- per-request view: fresh for own/propagated events, else lagged --
    slot = state.tick % D  # ring slot holding the snapshot from D ticks ago
    lag_exp = state.lag_expiry[slot]
    lag_ver = state.lag_version[slot]
    ki = cache_lib.key_index(keys)
    own = state.last_origin[ki] == proxy
    propagated = now_ms - state.last_event_ms[ki] >= gossip_ms
    if partitioned is not None:
        propagated = propagated & ~partitioned[proxy]
    fresh = own | propagated
    exp_view = jnp.where(fresh, sh.expiry_ms[ki], lag_exp[ki])
    ver_view = jnp.where(fresh, sh.cached_version[ki], lag_ver[ki])

    _, hit, stale = cache_lib.classify(
        exp_view, ver_view, sh.global_version[ki], mask, is_write, now_ms
    )

    # the per-key table writes (``scatter``) and the snapshot push
    # (``snapshot``) run under scopes of their own: the device trace
    # tells them apart (repro.obs.trace.phase_map)
    with jax.named_scope("scatter"):
        # --- converged-table effects: identical to the shared model ------
        new_sh, eff = cache_lib.apply_batch(
            sh,
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

        # --- gossip log: invalidations first, installs win on collision --
        # (same intra-tick order as apply_batch's table scatters)
        inv = cache_lib.key_index(eff.inv_keys)
        ins = cache_lib.key_index(eff.ins_keys)
        lev = state.last_event_ms.at[inv].set(now_ms, mode="drop")
        lor = state.last_origin.at[inv].set(proxy, mode="drop")
        lev = lev.at[ins].set(now_ms, mode="drop")
        lor = lor.at[ins].set(proxy, mode="drop")

    # --- push the post-tick snapshot; this slot is re-read at tick+D -----
    with jax.named_scope("snapshot"):
        lag_e = state.lag_expiry.at[slot].set(new_sh.expiry_ms)
        lag_v = state.lag_version.at[slot].set(new_sh.cached_version)

    # --- per-proxy counters: segment-sum flags onto the proxy axis -------
    # miss/bypassed come from apply_batch's effect vectors, so per-proxy
    # counters sum to the aggregate ones by construction.
    def seg(flags: jnp.ndarray) -> jnp.ndarray:
        sink = jnp.where(flags, proxy, P)  # OOB sentinel drops non-events
        return jnp.zeros((P,), jnp.int32).at[sink].add(1, mode="drop")

    new = state._replace(
        shared=new_sh,
        tick=state.tick + 1,
        last_event_ms=lev,
        last_origin=lor,
        lag_expiry=lag_e,
        lag_version=lag_v,
        hits_p=state.hits_p + seg(hit),
        misses_p=state.misses_p + seg(eff.miss),
        stale_p=state.stale_p + seg(stale),
        bypasses_p=state.bypasses_p + seg(eff.bypassed),
    )
    return new, hit


def remap_invalidate(
    state: FleetState, moved: jnp.ndarray
) -> FleetState:
    """Fleet-wide remap invalidation: after a membership epoch flip,
    NO proxy may serve an entry whose owner changed without
    revalidation (the tested property).  Moved entries are dropped from
    the converged table (:func:`repro.core.cache.remap_invalidate`) AND
    from every lagged snapshot in the ring buffer — whichever view a
    proxy's gossip freshness test selects, the entry is never-live."""
    return state._replace(
        shared=cache_lib.remap_invalidate(state.shared, moved),
        lag_expiry=jnp.where(
            cache_lib.to_table(moved, False), 0.0, state.lag_expiry
        ),
    )


def remap_counted(
    state: FleetState, moved: jnp.ndarray, now_ms: jnp.ndarray
) -> Tuple[FleetState, jnp.ndarray]:
    """:func:`remap_invalidate`, and the () float32 count of the entries
    it drops that were still live at ``now_ms``, in the converged table
    and in each lagged snapshot."""
    mv = cache_lib.to_table(moved, False)
    n = jnp.sum(mv & (state.shared.expiry_ms > now_ms)) + jnp.sum(
        mv & (state.lag_expiry > now_ms)
    )
    # the count reads the tables before the remap rewrites them; the
    # barrier orders it first, so the remap still writes in place
    n, expiry, lag = jax.lax.optimization_barrier(
        (n.astype(jnp.float32), state.shared.expiry_ms, state.lag_expiry)
    )
    state = state._replace(
        shared=state.shared._replace(expiry_ms=expiry), lag_expiry=lag
    )
    return remap_invalidate(state, moved), n


def slow_fleet(
    state: FleetState,
    window_ms: float,
    rtt_ms: float,
    lease_remaining_ms: float = jnp.inf,
    p_star: float = cache_lib.P_STAR,
    ttl_scale=1.0,
) -> FleetState:
    """T_slow retune: the hazard estimator lives on the converged table
    (server-side aggregates, which gossip does not lag).  ``ttl_scale``
    is the controller-emitted TTL multiplier (``Knobs.ttl_scale``)."""
    shared = cache_lib.slow_update(
        state.shared,
        window_ms,
        rtt_ms,
        lease_remaining_ms,
        p_star,
        ttl_scale=ttl_scale,
    )
    return state._replace(shared=shared)
