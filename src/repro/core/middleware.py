"""Middleware pipeline: composable batch stages in front of routing.

The paper frames MIDAS as *middleware* — stages that sit between incoming
metadata requests and the routing decision.  Each stage sees the tick's
request batch, may absorb requests (serve them at the proxy) by clearing
their mask bits, and carries its own state through the scan.  Stages also
get a slow-loop hook on the paper's T_slow cadence.

``SimConfig.middleware`` is a tuple of registered stage names applied in
order; the cooperative cache is the first (and reference) stage.  Writing
a new stage — admission control, QoS throttling (PADLL-style), in-network
caching (Fletch-style) — means subclassing :class:`Middleware`,
registering it, and naming it in the config; the simulator core never
changes.

Scan contract (DESIGN.md §9): ``on_batch``/``on_slow`` execute inside the
engine's jitted tick scan, so a stage's state must keep a stable pytree
structure (same leaves, shapes, dtypes) across calls, and per-tick Python
side effects will only run at trace time.  The stage loop itself is
Python-unrolled — pipelines are short and heterogeneous — but each
stage's body is traced once per compile, independent of the horizon.

    from repro.core import middleware

    @middleware.register("drop_writes")
    class DropWrites(middleware.Middleware):
        def on_batch(self, state, batch, cfg):
            keep = batch.mask & ~batch.is_write
            absorbed = jnp.sum(batch.mask & batch.is_write)
            return state, keep, absorbed.astype(jnp.float32)

    SimConfig(middleware=("drop_writes", "cache"))
"""

from __future__ import annotations

from typing import Any, NamedTuple, Tuple, Type

import jax.numpy as jnp

from repro.core import cache as cache_lib
from repro.core import fleet as fleet_lib
from repro.core import registry as registry_lib
from repro.core.controllers.base import T_SLOW_MS, Knobs


class BatchView(NamedTuple):
    """One tick's request batch, as seen by a middleware stage."""

    keys: jnp.ndarray      # (R,) int32 namespace keys
    mask: jnp.ndarray      # (R,) bool validity (may be narrowed upstream)
    is_write: jnp.ndarray  # (R,) bool metadata-mutating ops
    now_ms: jnp.ndarray    # () float32 tick clock
    rng: jnp.ndarray       # per-stage PRNG key
    # fault context (faults.FaultTickInfo) or None when the run carries
    # no fault schedule — stages read availability / partition state
    # from here; None keeps the zero-fault path untouched
    faults: Any = None


class Middleware:
    """Base class for registered pipeline stages.

    ``init(cfg) -> state`` builds the stage's carried pytree.
    ``on_batch(state, batch, cfg) -> (state, mask, absorbed)`` processes
    one tick: the returned mask replaces ``batch.mask`` for downstream
    stages and routing; ``absorbed`` is the () float32 count of requests
    served at the proxy.  ``on_slow(state, cfg, knobs) -> state`` runs
    on the T_slow cadence; ``knobs`` is the configured controller's
    emitted :class:`repro.core.controllers.Knobs` bundle (the cache
    stages consume ``knobs.ttl_scale``).
    """

    name: str = "?"

    def init(self, cfg) -> Any:
        return ()

    def on_batch(
        self, state: Any, batch: BatchView, cfg
    ) -> Tuple[Any, jnp.ndarray, jnp.ndarray]:
        return state, batch.mask, jnp.zeros((), jnp.float32)

    def on_slow(self, state: Any, cfg, knobs: Knobs) -> Any:
        return state

    def on_fault(self, state: Any, info, cfg, now_ms) -> Tuple[Any, Any]:
        """React to this tick's fault context (``info`` is a
        ``faults.FaultTickInfo``), called BEFORE ``on_batch`` so remap
        invalidation lands before any request of the new epoch is
        served.  Runs inside the jitted scan only when the config
        carries a membership-changing fault schedule.  Returns the new
        state and the () float32 count of the entries it dropped that
        were still live at ``now_ms``, over every view the stage serves
        from, or None for a stage that keeps no per-key entries;
        default: ``(state, None)``.
        """
        return state, None


REGISTRY = registry_lib.Registry("middleware")


def register(name: str):
    """Class decorator registering a Middleware stage under ``name``."""
    return REGISTRY.register(name)


def unregister(name: str) -> None:
    REGISTRY.unregister(name)


def available() -> Tuple[str, ...]:
    return REGISTRY.available()


def get_class(name: str) -> Type[Middleware]:
    return REGISTRY.get_class(name)


def get(name: str) -> Middleware:
    return REGISTRY.get(name)


@register("cache")
class CooperativeCache(Middleware):
    """The paper's cooperative metadata cache as a pipeline stage.

    Read hits within the validity horizon are absorbed at the proxy;
    writes always pass through (bumping versions / invalidating leases).
    The slow hook retunes the aggregate TTL from the invalidation-hazard
    estimator.  Coherence semantics live unchanged in
    :mod:`repro.core.cache`.
    """

    def init(self, cfg) -> cache_lib.CacheState:
        return cache_lib.init_cache(cfg.N)

    def on_batch(self, state: cache_lib.CacheState, batch: BatchView, cfg):
        fi = batch.faults
        state, hit = cache_lib.lookup_batch(
            state,
            batch.keys,
            batch.mask,
            batch.is_write,
            batch.now_ms,
            mode=cfg.cache_mode,
            lease_ms=cfg.lease_ms,
            rtt_ms=cfg.rtt_ms,
            p_star=cfg.p_star,
            avail=None if fi is None else fi.avail,
        )
        # hits never reach the servers
        return state, batch.mask & ~hit, jnp.sum(hit).astype(jnp.float32)

    def on_fault(self, state: cache_lib.CacheState, info, cfg, now_ms):
        if info.inval is None:
            return state, None
        return cache_lib.remap_counted(state, info.inval, now_ms)

    def on_slow(self, state: cache_lib.CacheState, cfg, knobs: Knobs):
        lease = cfg.lease_ms if cfg.cache_mode == "lease" else jnp.inf
        return cache_lib.slow_update(
            state,
            T_SLOW_MS,
            cfg.rtt_ms,
            lease,
            cfg.p_star,
            ttl_scale=knobs.ttl_scale,
        )


@register("fleet_cache")
class FleetCache(Middleware):
    """The cooperative cache as ``cfg.P`` real proxies with gossip.

    Requests are sharded across the fleet per tick (slot r → proxy
    (r+tick)%P); each proxy decides hits against its own gossip-delayed
    view (``cfg.gossip_ms`` propagation, see :mod:`repro.core.fleet`),
    while effects land on the converged table.  At ``gossip_ms=0`` this
    stage reproduces ``"cache"`` bit-for-bit — the Δ=0 equivalence
    contract.
    """

    def init(self, cfg) -> fleet_lib.FleetState:
        D = fleet_lib.delay_ticks(cfg.gossip_ms, cfg.dt_ms)
        return fleet_lib.init_fleet(cfg.N, cfg.P, D)

    def on_batch(self, state: fleet_lib.FleetState, batch: BatchView, cfg):
        R = batch.keys.shape[0]
        proxy = fleet_lib.proxy_assign(R, cfg.P, state.tick)
        fi = batch.faults
        state, hit = fleet_lib.lookup_fleet(
            state,
            batch.keys,
            batch.mask,
            batch.is_write,
            proxy,
            batch.now_ms,
            mode=cfg.cache_mode,
            lease_ms=cfg.lease_ms,
            rtt_ms=cfg.rtt_ms,
            p_star=cfg.p_star,
            gossip_ms=cfg.gossip_ms,
            partitioned=None if fi is None else fi.partition,
            avail=None if fi is None else fi.avail,
        )
        # hits are served by their proxy and never reach the servers
        return state, batch.mask & ~hit, jnp.sum(hit).astype(jnp.float32)

    def on_fault(self, state: fleet_lib.FleetState, info, cfg, now_ms):
        if info.inval is None:
            return state, None
        return fleet_lib.remap_counted(state, info.inval, now_ms)

    def on_slow(self, state: fleet_lib.FleetState, cfg, knobs: Knobs):
        lease = cfg.lease_ms if cfg.cache_mode == "lease" else jnp.inf
        return fleet_lib.slow_fleet(
            state,
            T_SLOW_MS,
            cfg.rtt_ms,
            lease,
            cfg.p_star,
            ttl_scale=knobs.ttl_scale,
        )
