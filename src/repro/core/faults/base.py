"""Fault-event schema, registry, and the host-side schedule compiler.

A *fault* is a typed, registered event — ``proxy_crash``,
``server_brownout``, ``gossip_partition``, ... — injected into a run via
``SimConfig(faults=(...,))``.  The design constraint is the scan contract
(DESIGN.md §9): the engine's tick is jitted and sweep-vmapped, so fault
dynamics cannot branch on Python state at run time.  Instead the whole
fault program is compiled HERE, host-side, into dense time-indexed numpy
schedules (ground-truth membership, service-rate scale, gossip
partitions, storm intensity), which ride the tick scan's ``xs`` exactly
like the tick clock does — unbatched under sweep vmaps, constant-folded
where inert.

Two-plane semantics.  ``member`` is ground truth: a crashed server
serves zero requests immediately.  ``detected`` is what the *proxies*
believe: a server is presumed alive until it has been silent for
``DETECT_TIMEOUT_MS`` (the same windowed-heartbeat rule as
:class:`repro.ft.failures.FailureDetector`, property-tested against it).
Routing, feasible sets, remap invalidation, and the controller's
availability signal all follow ``detected`` — the detection latency is
precisely the hotspot window the resilience benchmark (E12) measures.

Membership epochs.  Consecutive runs of identical ``detected`` rows form
*epochs*; per-epoch subrings are built once (numpy) and the per-key
primary owner per epoch (``owner_by_epoch``) is the compile-time table
the engine diffs on an epoch flip to derive the remap-invalidation mask:
exactly the keys whose owner changed get dropped from every cache view
(consistent-hashing minimal disruption, tested as a property).

Zero-cost-when-off.  ``compile_faults`` returns ``None`` for an absent
or empty schedule, and every behavioural hook in the engine is gated on
the concrete ``has_*`` flags computed from the numpy schedules — a
benign (never-firing) schedule takes value-identical paths, so the
PR 5 golden engine is reproduced bit-for-bit (tested).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashring
from repro.core import registry as registry_lib
from repro.obs import trace as obs_trace

# Detection timeout: a member silent for longer is presumed FAILED (the
# host-side reference is repro.ft.failures.FailureDetector).
DETECT_TIMEOUT_MS = 500.0
# Signals.avail below this means "detected membership degraded" — the
# cache install guard and availability-aware controllers key off it.
AVAIL_FULL = 1.0 - 1e-6
# Writer lanes a fleet-scale checkpoint storm hammers (the hot-key lane
# pattern of benchmarks/ckpt_storm.py, promoted to a registered fault).
STORM_LANES = 16


@dataclasses.dataclass(frozen=True)
class FaultEvent:
    """One scheduled fault occurrence (hashable: rides ``SimConfig``).

    ``t0``/``duration`` are in ticks; ``duration <= 0`` means "until the
    end of the horizon".  ``target`` selects a server (or proxy, for
    ``gossip_partition``); ``-1`` picks each kind's documented default.
    ``magnitude`` is the kind-specific intensity in (0, 1].
    """

    kind: str
    t0: int = 100
    duration: int = 200
    target: int = -1
    magnitude: float = 0.5


class Schedule:
    """Mutable host-side schedule the registered specs write into."""

    def __init__(self, T: int, m: int, P: int):
        self.T, self.m, self.P = T, m, P
        self.member = np.ones((T, m), bool)
        self.service_scale = np.ones((T, m), np.float32)
        self.partition = np.zeros((T, P), bool)
        self.storm = np.zeros((T,), np.float32)
        self.active = np.zeros((T,), bool)

    def window(self, ev: FaultEvent) -> Tuple[int, int]:
        """[t0, t1) clipped to the horizon; open-ended when duration<=0."""
        t0 = max(int(ev.t0), 0)
        t1 = self.T if ev.duration <= 0 else min(t0 + int(ev.duration),
                                                 self.T)
        return min(t0, self.T), max(min(t0, self.T), t1)


class FaultSpec:
    """Base class for registered fault kinds.

    ``validate(ev, m, P)`` raises ``ValueError`` on a bad event at
    ``SimConfig`` construction time; ``apply(ev, sched)`` writes the
    event's effect into the host-side :class:`Schedule`.
    """

    kind: str = "?"

    def validate(self, ev: FaultEvent, m: int, P: int) -> None:
        pass

    def apply(self, ev: FaultEvent, sched: Schedule) -> None:
        raise NotImplementedError


REGISTRY = registry_lib.Registry("fault", name_attr="kind")


def register(kind: str):
    """Class decorator: ``@faults.register("my_fault")`` adds a
    FaultSpec subclass under ``kind`` (``SimConfig(faults=(kind,))``)."""
    return REGISTRY.register(kind)


def unregister(kind: str) -> None:
    """Remove a registered fault kind (intended for tests/plugins)."""
    REGISTRY.unregister(kind)


def available() -> Tuple[str, ...]:
    """Sorted names of every registered fault kind."""
    return REGISTRY.available()


def get_class(kind: str) -> Type[FaultSpec]:
    return REGISTRY.get_class(kind)


def get(kind: str) -> FaultSpec:
    """Instantiate the spec registered under ``kind``."""
    return REGISTRY.get(kind)


def normalize(faults) -> Tuple[FaultEvent, ...]:
    """Canonical event tuple: names become default-parameter events,
    and ``"kind:t0=...,duration=...,target=...,magnitude=..."`` strings
    (:func:`parse_fault`) the events they spell, equal to the
    ``FaultEvent`` built directly with those parameters.

    Cascade entries (:class:`programs.CascadeEvent`) pass through — they
    stay unresolved until the compiler knows ``dt_ms`` and the horizon
    (detection time is a wall-clock property, not a config one).
    """
    if not faults:
        return ()
    from repro.core.faults import programs  # lazy: programs imports base

    out = []
    for f in faults:
        if isinstance(f, str):
            f = parse_fault(f) if ":" in f else FaultEvent(kind=f)
        elif not isinstance(f, (FaultEvent, programs.CascadeEvent)):
            raise ValueError(
                f"SimConfig.faults entries must be fault names, "
                f"FaultEvent, or CascadeEvent, got {f!r}"
            )
        out.append(f)
    return tuple(out)


def _validate_one(ev: FaultEvent, m: int, P: int) -> None:
    get_class(ev.kind)  # raises with alternatives on unknown kind
    if ev.t0 < 0:
        raise ValueError(f"fault t0 must be >= 0, got {ev!r}")
    get(ev.kind).validate(ev, m, P)


def validate_events(faults, m: int, P: int) -> None:
    """Eager list-alternatives validation (SimConfig.__post_init__)."""
    from repro.core.faults import programs  # lazy: programs imports base

    for ev in normalize(faults):
        if isinstance(ev, programs.CascadeEvent):
            if ev.offset < 0:
                raise ValueError(f"cascade offset must be >= 0, got {ev!r}")
            _validate_one(ev.trigger, m, P)
            # the effect's t0 is a placeholder resolve() overwrites, so
            # only its kind-specific parameters are checked here
            get_class(ev.effect.kind)
            get(ev.effect.kind).validate(ev.effect, m, P)
        else:
            _validate_one(ev, m, P)


def parse_fault(spec: str) -> FaultEvent:
    """Parse ``"kind"`` or ``"kind:t0=200,duration=300,..."`` (CLI)."""
    spec = spec.strip()
    kind, _, rest = spec.partition(":")
    if kind not in REGISTRY:
        raise ValueError(
            f"unknown fault {kind!r}; available: {', '.join(available())}"
        )
    kw: Dict[str, Any] = {}
    fields = {f.name: f.type for f in dataclasses.fields(FaultEvent)}
    for tok in filter(None, (t.strip() for t in rest.split(","))):
        k, sep, v = tok.partition("=")
        if not sep or k not in fields or k == "kind":
            raise ValueError(
                f"bad fault parameter {tok!r} in {spec!r}; expected "
                f"key=value with key in t0, duration, target, magnitude"
            )
        try:
            kw[k] = float(v) if k == "magnitude" else int(v)
        except ValueError:
            raise ValueError(
                f"bad fault parameter {tok!r} in {spec!r}: {k} takes "
                f"{'a number' if k == 'magnitude' else 'a whole number'}"
            ) from None
    return FaultEvent(kind=kind, **kw)


# ---------------------------------------------------------------------------
# Detection, epochs, and the compiled schedule
# ---------------------------------------------------------------------------


def detect_ticks(dt_ms: float) -> int:
    """Detection timeout in whole ticks (>= 1)."""
    return max(int(math.ceil(DETECT_TIMEOUT_MS / dt_ms)), 1)


def detect_available(member: np.ndarray, timeout_ticks: int) -> np.ndarray:
    """(T, m) detected-alive mask from ground-truth membership.

    A member is detected alive at tick t iff it heartbeat within the
    last ``timeout_ticks`` ticks (inclusive window [t-K, t]), with every
    member presumed alive before t=0 — the same rule as
    ``FailureDetector.failed`` with injected clocks (property-tested).
    """
    member = np.asarray(member, bool)
    T, m = member.shape
    ext = np.concatenate([np.ones((timeout_ticks, m), bool), member])
    det = np.zeros((T, m), bool)
    for j in range(timeout_ticks + 1):
        det |= ext[j:j + T]
    return det


def _epochs(detected: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Collapse (T, m) detected rows into (epoch_masks, epoch_index)."""
    T = detected.shape[0]
    masks = [detected[0]]
    idx = np.zeros((T,), np.int32)
    for t in range(1, T):
        if not np.array_equal(detected[t], masks[-1]):
            masks.append(detected[t])
        idx[t] = len(masks) - 1
    return np.stack(masks), idx


def _scan_width(m: int, V: int, masks: np.ndarray) -> int:
    """Feasible-set window wide enough to find d_max live owners: the
    default 16 slots, stretched by the worst epoch's dead fraction."""
    min_live = max(min(int(mk.sum()) for mk in masks), 1)
    return int(min(max(16, math.ceil(16 * m / min_live)), m * V))


class CompiledFaults(NamedTuple):
    """Host-compiled fault program for one (config, horizon) pair.

    All arrays are concrete numpy — they enter jitted code as constants
    (via :func:`make_xs`) or compile-time tables (``owner_by_epoch``).
    The ``has_*`` flags are Python bools: the engine's fault hooks are
    gated on them at trace time, so inert schedules cost nothing.
    """

    member: np.ndarray          # (T, m) bool ground-truth membership
    service_scale: np.ndarray   # (T, m) f32 service-rate multiplier
    partition: np.ndarray       # (T, P) bool gossip-partitioned proxies
    storm: np.ndarray           # (T,) f32 storm intensity in [0, 1]
    detected: np.ndarray        # (T, m) bool detected membership
    avail: np.ndarray           # (T,) f32 detected live fraction
    epoch: np.ndarray           # (T,) i32 membership epoch index
    epoch_prev: np.ndarray      # (T,) i32 previous tick's epoch
    epoch_masks: np.ndarray     # (E, m) bool detected mask per epoch
    owner_by_epoch: Optional[np.ndarray]  # (E, N) i32 primary per epoch
    active: np.ndarray          # (T,) bool any event window active
    timeout_ticks: int          # detection window K
    scan_width: int             # member-aware feasible-set window
    has_downtime: bool          # any ground-truth dead tick
    has_remap: bool             # >1 detected-membership epoch
    has_brownout: bool          # any service_scale != 1
    has_partition: bool         # any partitioned (proxy, tick)
    has_storm: bool             # any storm intensity > 0


class FaultXs(NamedTuple):
    """Per-tick fault rows riding the scan ``xs`` (leading T axis)."""

    member: jnp.ndarray      # (T, m) bool
    scale: jnp.ndarray       # (T, m) f32
    detected: jnp.ndarray    # (T, m) bool
    avail: jnp.ndarray       # (T,) f32
    partition: jnp.ndarray   # (T, P) bool
    epoch: jnp.ndarray       # (T,) i32
    epoch_prev: jnp.ndarray  # (T,) i32


class FaultTickInfo(NamedTuple):
    """One tick's fault context, handed to middleware via BatchView."""

    member: jnp.ndarray     # (m,) bool ground truth
    detected: jnp.ndarray   # (m,) bool detected membership
    partition: jnp.ndarray  # (P,) bool partitioned proxies
    avail: jnp.ndarray      # () f32 detected live fraction
    inval: Optional[jnp.ndarray]  # (N,) bool owner-changed keys


@functools.lru_cache(maxsize=None)
def _compile_cached(cfg, T: int) -> CompiledFaults:
    # the host span of the uncached compile: at N=10^6 it builds one
    # (N,) owner table per epoch in numpy
    with obs_trace.span("faults/compile", cat="compile", T=T, N=cfg.N):
        return _compile(cfg, T)


def _compile(cfg, T: int) -> CompiledFaults:
    from repro.core.faults import programs  # lazy: programs imports base

    # cascade entries resolve HERE: detection time needs dt_ms + horizon
    events = programs.resolve(
        normalize(cfg.faults), dt_ms=cfg.dt_ms, T=T, m=cfg.m, P=cfg.P
    )
    sched = Schedule(T, cfg.m, cfg.P)
    for ev in events:
        get(ev.kind).apply(ev, sched)
    K = detect_ticks(cfg.dt_ms)
    detected = detect_available(sched.member, K)
    masks, epoch = _epochs(detected)
    for mk in masks:
        if not mk.any():
            raise ValueError(
                "fault schedule leaves no detected-live server in some "
                "epoch; keep at least one member alive"
            )
    epoch_prev = np.concatenate([epoch[:1], epoch[:-1]])
    has_remap = masks.shape[0] > 1
    owner_by_epoch = None
    if has_remap:
        keys = np.arange(cfg.N)
        owner_by_epoch = np.stack([
            hashring.np_member_primary(cfg.m, cfg.V, mk, keys)
            for mk in masks
        ]).astype(np.int32)
    return CompiledFaults(
        member=sched.member,
        service_scale=sched.service_scale,
        partition=sched.partition,
        storm=sched.storm,
        detected=detected,
        avail=detected.mean(axis=1).astype(np.float32),
        epoch=epoch,
        epoch_prev=epoch_prev.astype(np.int32),
        epoch_masks=masks,
        owner_by_epoch=owner_by_epoch,
        active=sched.active,
        timeout_ticks=K,
        scan_width=_scan_width(cfg.m, cfg.V, masks),
        has_downtime=bool((~sched.member).any()),
        has_remap=has_remap,
        has_brownout=bool((sched.service_scale != 1.0).any()),
        has_partition=bool(sched.partition.any()),
        has_storm=bool((sched.storm > 0.0).any()),
    )


def counters(fc: CompiledFaults, R: int) -> Dict[str, Any]:
    """What a compiled fault program does over its horizon, for a grid
    of ``R`` request slots per tick: ``epochs``; ``flip_ticks``, the
    ticks whose epoch differs from the previous tick's, and
    ``moved_keys``, the keys whose detected-ring owner changes at each
    (the remap invalidation's size); ``server_ticks_down`` (ground-truth
    dead server-ticks); ``detect_delay_ticks``, the ticks from each
    crash to its detection (a crash over before its detection has
    none); ``storm_ticks``; and ``storm_slots``, the slot-ticks the
    storm overlay opens (:func:`apply_traffic`: each turns into a write
    where the workload leaves its slot idle)."""
    flips = np.flatnonzero(fc.epoch != fc.epoch_prev)
    moved = []
    if fc.owner_by_epoch is not None:
        own = fc.owner_by_epoch
        moved = [
            int((own[fc.epoch[t]] != own[fc.epoch_prev[t]]).sum())
            for t in flips
        ]
    delays = []
    for j in range(fc.member.shape[1]):
        # each run of dead ticks [t0, t1) of server j
        edges = np.diff(np.r_[0, (~fc.member[:, j]).astype(np.int8), 0])
        for t0, t1 in zip(np.flatnonzero(edges == 1),
                          np.flatnonzero(edges == -1)):
            seen = np.flatnonzero(~fc.detected[t0:t1, j])
            if seen.size:
                delays.append(int(seen[0]))
    r = np.arange(R)
    tail = (R - r.astype(np.float32) - 0.5) / R
    return {
        "epochs": int(fc.epoch_masks.shape[0]),
        "flip_ticks": [int(t) for t in flips],
        "moved_keys": moved,
        "server_ticks_down": int((~fc.member).sum()),
        "detect_delay_ticks": delays,
        "storm_ticks": int((fc.storm > 0).sum()),
        "storm_slots": int((tail[None, :] < fc.storm[:, None]).sum()),
    }


def compile_faults(cfg, T: int) -> Optional[CompiledFaults]:
    """The compiled fault program for ``cfg`` over a T-tick horizon, or
    ``None`` when the config carries no fault events (``faults=None``
    and ``faults=()`` are both the identically-untouched engine)."""
    if not normalize(cfg.faults):
        return None
    return _compile_cached(cfg, int(T))


def make_xs(fc: CompiledFaults) -> FaultXs:
    """Device-side per-tick rows appended to the scan's xs tuple."""
    return FaultXs(
        member=jnp.asarray(fc.member),
        scale=jnp.asarray(fc.service_scale),
        detected=jnp.asarray(fc.detected),
        avail=jnp.asarray(fc.avail, jnp.float32),
        partition=jnp.asarray(fc.partition),
        epoch=jnp.asarray(fc.epoch, jnp.int32),
        epoch_prev=jnp.asarray(fc.epoch_prev, jnp.int32),
    )


def tick_info(fc: CompiledFaults, fx: FaultXs) -> FaultTickInfo:
    """One tick's fault context (``fx`` holds this tick's slices).

    The remap-invalidation mask diffs the per-epoch owner tables at the
    current vs. previous epoch — all-False except on a flip tick, where
    it marks exactly the keys whose detected-ring owner changed.  The
    diff runs under the named scope ``inval``.
    """
    inval = None
    if fc.has_remap:
        with jax.named_scope("inval"):
            owners = jnp.asarray(fc.owner_by_epoch)
            inval = owners[fx.epoch] != owners[fx.epoch_prev]
    return FaultTickInfo(
        member=fx.member,
        detected=fx.detected,
        partition=fx.partition,
        avail=fx.avail,
        inval=inval,
    )


def feasible_by_epoch(
    ring: hashring.Ring, keysg: jnp.ndarray, d_max: int, fc: CompiledFaults
) -> jnp.ndarray:
    """Membership-aware feasible sets for a whole (T, ...) key grid.

    One batched member-aware gather per epoch (E is tiny — one per
    membership change), then a per-tick row gather selects each tick's
    epoch — the scan engine's hoisted-feasible contract, now membership-
    aware, still O(1) trace size in T.
    """
    if not fc.has_remap:
        return hashring.feasible_set(ring, keysg, d_max)
    stacks = [
        hashring.feasible_set(
            ring, keysg, d_max,
            scan_width=fc.scan_width, member=jnp.asarray(mk),
        )
        for mk in fc.epoch_masks
    ]
    T = keysg.shape[0]
    return jnp.stack(stacks)[jnp.asarray(fc.epoch), jnp.arange(T)]


def apply_traffic(
    fc: CompiledFaults,
    keys: jnp.ndarray,
    mask: jnp.ndarray,
    is_write: jnp.ndarray,
):
    """Overlay storm traffic on a (T, R) workload grid.

    A storm of intensity s activates the trailing s-fraction of each
    tick's inactive request slots as WRITES against the hot writer-lane
    keys (r mod STORM_LANES) — the ckpt_storm lane pattern at fleet
    scale.  Inactive-tail slots keep the base workload untouched.
    """
    if not fc.has_storm:
        return keys, mask, is_write
    s = jnp.asarray(fc.storm)[:, None]
    R = keys.shape[-1]
    r = jnp.arange(R, dtype=jnp.int32)
    tail_frac = (R - r.astype(jnp.float32) - 0.5) / R
    extra = (~mask) & (tail_frac[None, :] < s)
    lane_keys = (r % STORM_LANES).astype(keys.dtype)
    keys = jnp.where(extra, lane_keys[None, :], keys)
    return keys, mask | extra, is_write | extra
