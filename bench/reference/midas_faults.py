"""Plain reference of the MIDAS simulator under a fault program: the
reference module ``midas_faults`` (a configuration names it under
``"reference"``).

Written from the fault layer's stated semantics, independent of the code
under test: it imports nothing of the program.  What the fault-free
simulator shares with it (the consistent-hash ring, the fleet cache and
gossip state, the routing wave, the latency sketch, the control
constants and the control targets) it takes from the plain reference
``midas_queue.py`` beside it, loaded as a file.  Its own are the fault
program and the tick that runs under it:

* events spelled ``"kind:t0=...,duration=...,target=...,magnitude=..."``
  (ticks; a missing parameter takes its default t0 100, duration 200,
  target -1, magnitude 0.5; ``duration <= 0`` runs to the horizon's
  end), of two kinds: ``proxy_crash`` (server ``target``, -1 the last,
  is down for the window) and ``ckpt_storm_fleet`` (storm intensity
  ``magnitude`` for the window, the largest where storms overlap);
* ground-truth membership per tick, and the detected membership: a
  server is detected alive at tick t iff it was up at some tick of
  [t - K, t], K = ceil(500 ms / dt) ticks, with every server presumed up
  before t = 0; the detected live fraction ``avail``;
* membership epochs, the runs of identical detected rows; with more
  than one, each epoch's feasible sets are the first ``d_max`` distinct
  *live* owners clockwise of a key in a window of
  ``min(max(16, ceil(16 m / min_live)), m V)`` ring slots (``min_live``
  the fewest detected-live servers of any epoch), padded, where fewer
  are found, with the first live servers along (ring primary + i) mod
  m, the first of them repeated where fewer than ``d_max`` live;
* the storm overlay: at intensity s, each request slot r of the tick's
  R whose tail position (R - r - 1/2) / R is under s and which the
  workload leaves idle becomes a write to writer-lane key r mod 16;
* a down server serves nothing (its queue freezes until it rejoins),
  and requests are routed to it until it is detected down;
* at each epoch flip, before any request is served, every key whose
  primary on the detected ring (the live servers' virtual nodes alone)
  changed is dropped from the converged cache table and from every
  lagged snapshot: its entry is never live until reinstalled; the row
  field ``remap_dropped`` counts the dropped entries, over the table
  and the snapshots, whose expiry was still ahead of the tick's clock;
* while ``avail < 1 - 1e-6`` the cache installs nothing;
* the controller sees the detected membership: with more than one
  epoch the imbalance is that of the detected-live servers' queue view,
  and while ``avail`` is degraded the hysteresis controller escalates
  at once on each fast tick and never de-escalates.

It covers the policy stack the fault cells run: ``midas`` routing, one
wave per proxy (``fleet_routing``), the ``fleet_cache`` and the
``hysteresis`` controller; ``deployment`` refuses the rest, naming it.
``dtype`` sets the precision of every float the simulation computes, as
in ``midas_queue``; the schedule is exact (booleans and ticks).
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import math
import sys
from pathlib import Path
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _load_queue():
    """``midas_queue.py`` from beside this file, registered under a name
    of its own (dataclasses look their module up in ``sys.modules``)."""
    path = Path(__file__).resolve().with_name("midas_queue.py")
    name = f"{__name__}_midas_queue"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


mq = _load_queue()

# Detection timeout and the degraded-availability threshold.
DETECT_TIMEOUT_MS = 500.0
AVAIL_FULL = 1.0 - 1e-6
# The storm's writer lanes: storm writes go to keys r mod STORM_LANES.
STORM_LANES = 16
# The event parameters and their defaults.
EVENT_DEFAULTS = {"t0": 100, "duration": 200, "target": -1,
                  "magnitude": 0.5}
KINDS = ("proxy_crash", "ckpt_storm_fleet")

POLICIES = ("midas",)
MIDDLEWARE = (("fleet_cache",),)
# ``midas_queue``'s fields, and the cache entries still live that the
# remap invalidation dropped over the horizon (converged table and
# every lagged snapshot)
FIELDS = mq.FIELDS + (("remap_dropped", "remap_dropped_total"),)
targets = mq.targets


class Event(NamedTuple):
    kind: str
    t0: int
    duration: int
    target: int
    magnitude: float


@dataclasses.dataclass(frozen=True)
class Deployment(mq.Deployment):
    """``midas_queue``'s deployment and its fault program."""

    events: Tuple[Event, ...]


def parse_event(spec, m: int) -> Event:
    """One event string; a ``ValueError`` names what is not modelled."""
    if not isinstance(spec, str):
        raise ValueError(
            f"sim.faults entry {spec!r} (only event strings: no cascade)"
        )
    kind, _, rest = spec.strip().partition(":")
    if kind not in KINDS:
        raise ValueError(f"fault kind {kind!r} in {spec!r}")
    kw = dict(EVENT_DEFAULTS)
    for tok in filter(None, (t.strip() for t in rest.split(","))):
        k, sep, v = tok.partition("=")
        if not sep or k not in EVENT_DEFAULTS:
            raise ValueError(f"fault parameter {tok!r} in {spec!r}")
        try:
            kw[k] = float(v) if k == "magnitude" else int(v)
        except ValueError:
            raise ValueError(f"fault parameter {tok!r} in {spec!r}") \
                from None
    ev = Event(kind=kind, **kw)
    if ev.t0 < 0:
        raise ValueError(f"fault t0={ev.t0} in {spec!r}")
    if kind == "proxy_crash" and not -1 <= ev.target < m:
        raise ValueError(f"fault target={ev.target} in {spec!r}")
    if kind == "ckpt_storm_fleet" and not 0.0 < ev.magnitude <= 1.0:
        raise ValueError(f"fault magnitude={ev.magnitude} in {spec!r}")
    return ev


def deployment(sim: Dict, traffic: Dict) -> Deployment:
    """The deployment and fault program a configuration's ``sim``
    settings and a traffic file's policy stack state; a ``ValueError``
    names every key, value and event this reference does not model."""
    bad = [f"sim.{k} (missing)" for k in mq.SIM_KEYS if k not in sim]
    for k, v in sim.items():
        if k in mq.FIXED and v != mq.FIXED[k]:
            bad.append(f"sim.{k}={v!r} (only {mq.FIXED[k]!r})")
        elif k not in mq.FIXED and k not in mq.SIM_KEYS + ("faults",):
            bad.append(f"sim.{k}={v!r}")
    if sim.get("fleet_routing") is not True:
        bad.append(f"sim.fleet_routing={sim.get('fleet_routing')!r} "
                   f"(only True)")
    events = []
    if not sim.get("faults"):
        bad.append("sim.faults (missing: this reference runs a fault "
                   "program)")
    for spec in sim.get("faults") or ():
        try:
            events.append(parse_event(spec, int(sim.get("m", 1))))
        except ValueError as e:
            bad.append(str(e))
    policy = traffic["policy"]
    middleware = tuple(traffic["middleware"])
    controller = traffic.get("controller", "hysteresis")
    if policy not in POLICIES:
        bad.append(f"policy={policy!r}")
    if middleware not in MIDDLEWARE:
        bad.append(f"middleware={list(middleware)!r}")
    if controller not in mq.CONTROLLERS:
        bad.append(f"controller={controller!r}")
    if bad:
        raise ValueError(
            "reference midas_faults does not implement " + ", ".join(bad)
        )
    return Deployment(
        **{k: sim[k] for k in mq.SIM_KEYS},
        fixed_d=int(traffic.get("fixed_d", 2)),
        policy=policy,
        middleware=middleware,
        events=tuple(events),
    )


# ---------------------------------------------------------------------------
# The fault program (host, numpy)
# ---------------------------------------------------------------------------


class Schedule(NamedTuple):
    member: np.ndarray  # (T, m) bool ground truth
    detected: np.ndarray  # (T, m) bool detected membership
    avail: np.ndarray  # (T,) float32 detected live fraction
    storm: np.ndarray  # (T,) float32 storm intensity
    epoch: np.ndarray  # (T,) int32 membership epoch
    masks: np.ndarray  # (E, m) bool detected membership of each epoch
    scan_width: int  # feasible-set window under several epochs


def detect(member: np.ndarray, K: int) -> np.ndarray:
    """Detected alive at t iff up at some tick of [t - K, t], every
    server presumed up before t = 0."""
    T = member.shape[0]
    return np.stack([
        member[max(t - K, 0):t + 1].any(axis=0) | (t - K < 0)
        for t in range(T)
    ])


def schedule(dep: Deployment, T: int) -> Schedule:
    m = dep.m
    member = np.ones((T, m), bool)
    storm = np.zeros((T,), np.float32)
    for ev in dep.events:
        t0 = min(ev.t0, T)
        t1 = T if ev.duration <= 0 else min(t0 + ev.duration, T)
        if ev.kind == "proxy_crash":
            member[t0:t1, m - 1 if ev.target < 0 else ev.target] = False
        else:
            storm[t0:t1] = np.maximum(storm[t0:t1], ev.magnitude)
    K = max(math.ceil(DETECT_TIMEOUT_MS / dep.dt_ms), 1)
    detected = detect(member, K)
    if not detected.any(axis=1).all():
        raise ValueError("the fault program leaves no server detected up")
    new = np.r_[False, (detected[1:] != detected[:-1]).any(axis=1)]
    epoch = np.cumsum(new).astype(np.int32)
    masks = detected[np.r_[0, np.flatnonzero(new)]]
    min_live = int(masks.sum(axis=1).min())
    return Schedule(
        member=member,
        detected=detected,
        avail=detected.mean(axis=1).astype(np.float32),
        storm=storm,
        epoch=epoch,
        masks=masks,
        scan_width=min(max(16, math.ceil(16 * m / min_live)), m * dep.V),
    )


def live_primary(dep: Deployment, live: np.ndarray, keys) -> np.ndarray:
    """Each key's first owner clockwise on the ring of live servers'
    virtual nodes."""
    pos, owners = mq.ring(dep.m, dep.V)
    keep = live[owners]
    pos, owners = pos[keep], owners[keep]
    kp = mq._hash2(np.asarray(keys, np.uint32), 7919)
    return owners[np.searchsorted(pos, kp) % pos.size]


def live_feasible(dep: Deployment, keys, live: np.ndarray, width: int):
    """F(r) over the live servers in a ``width``-slot window, with the
    live fallback pad (module docstring)."""
    pos, owners = mq.ring(dep.m, dep.V)
    n = pos.size
    kp = mq._hash2(np.asarray(keys, np.uint32), 7919)
    base = np.searchsorted(pos, kp) % n
    cand = owners[(base[..., None] + np.arange(width)) % n]
    first = np.stack(
        [np.all(cand[..., :j] != cand[..., j:j + 1], axis=-1)
         for j in range(width)],
        axis=-1,
    )

    def ranked(ok, cands):
        rank = np.cumsum(ok, axis=-1) - 1
        out = np.full(keys.shape + (dep.d_max,), -1, np.int64)
        for r in range(dep.d_max):
            sel = ok & (rank == r)
            val = np.take_along_axis(cands, sel.argmax(-1)[..., None], -1)
            out[..., r] = np.where(sel.any(-1), val[..., 0], -1)
        return out

    out = ranked(first & live[cand], cand)
    walk = (cand[..., :1] + np.arange(dep.m)) % dep.m
    pad = ranked(live[walk], walk)
    pad = np.where(pad < 0, pad[..., :1], pad)
    return np.where(out < 0, pad, out).astype(np.int32)


@functools.lru_cache(maxsize=8)
def _program(dep: Deployment, T: int):
    """The schedule, each tick's index into the flips (-1 where the
    epoch does not change) and each flip's (N,) moved-key mask."""
    sch = schedule(dep, T)
    flips = np.flatnonzero(sch.epoch[1:] != sch.epoch[:-1]) + 1
    flip = np.full((T,), -1, np.int32)
    flip[flips] = np.arange(flips.size)
    keys = np.arange(dep.N)
    owner = [live_primary(dep, mk, keys) for mk in sch.masks]
    moved = np.stack(
        [owner[sch.epoch[t]] != owner[sch.epoch[t - 1]] for t in flips]
    ) if flips.size else np.zeros((0, dep.N), bool)
    return sch, flip, moved


def storm_overlay(storm, keys, mask, is_write):
    R = keys.shape[1]
    r = np.arange(R)
    tail = (R - r.astype(np.float32) - 0.5) / R
    extra = ~mask & (tail[None, :] < storm[:, None])
    keys = np.where(extra, (r % STORM_LANES).astype(keys.dtype), keys)
    return keys, mask | extra, is_write | extra


# ---------------------------------------------------------------------------
# One tick
# ---------------------------------------------------------------------------


def _cache_serve(dep, c, keys, mask, is_write, now, exp_view, ver_view,
                 avail):
    """``midas_queue``'s lease-mode cache tick with the install guard
    that degraded availability adds."""
    N = dep.N
    valid = mask & ~is_write
    hit = valid & (exp_view > now) & (ver_view >= 0)
    w = is_write & mask
    wk = jnp.where(w, keys, N)
    gv = c.gversion.at[wk].add(1, mode="drop")
    expiry = c.expiry.at[wk].set(0.0, mode="drop")
    n = c.win_writes + c.win_reads
    live = jnp.where(
        n >= mq.GUARD_MIN_EVENTS, c.win_writes / jnp.maximum(n, 1.0), 0.0
    )
    bypass = (jnp.maximum(c.write_frac, live) > mq.W_HIGH) | (
        avail < AVAIL_FULL
    )
    install = valid & ~hit & ~bypass
    ik = jnp.where(install, keys, N)
    expiry = expiry.at[ik].set(now + dep.lease_ms, mode="drop")
    version = c.version.at[ik].set(gv[jnp.minimum(ik, N - 1)], mode="drop")
    c = c._replace(
        expiry=expiry,
        version=version,
        gversion=gv,
        win_writes=c.win_writes + jnp.sum(w),
        win_reads=c.win_reads + jnp.sum(valid),
    )
    return c, hit, wk, ik


def _imbalance_live(x, live):
    """std / mean of the live servers' entries."""
    w = live.astype(x.dtype)
    n = jnp.maximum(jnp.sum(w), 1.0)
    mu = jnp.sum(x * w) / n
    var = jnp.sum(w * (x - mu) ** 2) / n
    return jnp.sqrt(var) / (mu + mq.EPS)


def _remap(s, gone, now):
    """Drop the moved keys from the converged table and every snapshot;
    also the count of the dropped entries that were live at ``now``."""
    c, g = s.cache, s.gossip
    dropped = jnp.sum(gone & (c.expiry > now)) + jnp.sum(
        gone[None, :] & (g.lag_expiry > now)
    )
    return s._replace(
        cache=c._replace(expiry=jnp.where(gone, 0.0, c.expiry)),
        gossip=g._replace(
            lag_expiry=jnp.where(gone[None, :], 0.0, g.lag_expiry)
        ),
    ), dropped.astype(s.L.dtype)


def _tick(dep, slots, edges, moved, s, acc, xs):
    """One tick under the fault program: remap on a flip, the fleet
    cache, one routing wave per proxy, service by the live servers,
    telemetry and the control loops.  Also returns the live entries the
    remap dropped."""
    t, keys, mask, is_write, feas, member, detected, avail, flip = xs
    F = s.L.dtype
    now = t.astype(F) * dep.dt_ms
    rng, _, r_route = jax.random.split(s.rng, 3)
    s = s._replace(rng=rng)
    several = moved.shape[0] > 0  # more than one epoch

    dropped = jnp.zeros((), F)
    if several:
        s, dropped = jax.lax.cond(
            flip >= 0,
            lambda s: _remap(s, moved[flip], now),
            lambda s: (s, dropped),
            s,
        )

    # the fleet cache: read hits are served at the proxy
    c, g = s.cache, s.gossip
    R = keys.shape[0]
    proxy = (jnp.arange(R, dtype=jnp.int32) + t) % dep.P
    lag = t % g.lag_expiry.shape[0]
    fresh = (g.origin[keys] == proxy) | (
        now - g.last_ms[keys] >= dep.gossip_ms
    )
    exp_view = jnp.where(fresh, c.expiry[keys], g.lag_expiry[lag][keys])
    ver_view = jnp.where(fresh, c.version[keys], g.lag_version[lag][keys])
    cache, hit, inv, ins = _cache_serve(
        dep, c, keys, mask, is_write, now, exp_view, ver_view, avail
    )
    last_ms = g.last_ms.at[inv].set(now, mode="drop")
    origin = g.origin.at[inv].set(proxy, mode="drop")
    s = s._replace(
        cache=cache,
        gossip=mq.Gossip(
            last_ms=last_ms.at[ins].set(now, mode="drop"),
            origin=origin.at[ins].set(proxy, mode="drop"),
            lag_expiry=g.lag_expiry.at[lag].set(cache.expiry),
            lag_version=g.lag_version.at[lag].set(cache.version),
        ),
    )
    mask = mask & ~hit
    hits = jnp.sum(hit).astype(F)

    # one routing wave per proxy, from its own staggered view
    def wave(carry, wx):
        s, sent, steered, eligible = carry
        g, idx = wx
        s, assign, st, el = mq._route_wave(
            dep, s, jax.random.fold_in(r_route, g), keys[idx], mask[idx],
            feas[idx], s.L_hat_p[(g + t) % dep.P], now,
        )
        m_ok = mask[idx]
        sent = sent.at[jnp.where(m_ok, assign, 0)].add(
            jnp.where(m_ok, 1.0, 0.0).astype(F)
        )
        return (s, sent, steered + st, eligible + el), None

    z = jnp.zeros((), F)
    (s, arrivals, steered, eligible), _ = jax.lax.scan(
        wave,
        (s, jnp.zeros((dep.m,), F), z, z),
        (jnp.arange(slots.shape[0], dtype=jnp.int32), slots),
    )

    # constant-rate servers; a down server serves nothing
    L = s.L + arrivals
    lat = (s.L + arrivals) * dep.service_ms
    rate = (dep.dt_ms / dep.service_ms) * member.astype(F)
    L = L - jnp.minimum(L, rate)
    s = s._replace(L=L)
    t1 = t + 1
    K = s.sk_buf.shape[1]
    s = s._replace(
        sk_buf=s.sk_buf.at[:, s.sk_n % K].set(lat), sk_n=s.sk_n + 1
    )
    phase = (jnp.arange(dep.P, dtype=jnp.int32) * dep.fast_ticks) // dep.P
    due = (t1 % dep.fast_ticks) == phase
    s = s._replace(
        L_hat_p=jnp.where(
            due[:, None], mq._ewma(s.L_hat_p, s.L[None, :]), s.L_hat_p
        )
    )

    def fast(s):
        p50_o, p99_o = mq._quantiles(s.sk_buf, s.sk_n)
        L_hat = jnp.mean(s.L_hat_p, axis=0)
        p50 = mq._ewma(s.p50, p50_o)
        p99 = mq._ewma(s.p99, p99_o)
        B = _imbalance_live(L_hat, detected) if several else (
            mq._imbalance(L_hat)
        )
        jitter = jax.random.uniform(
            jax.random.fold_in(s.rng, 3), (), minval=-1.0, maxval=1.0
        ).astype(F)
        pr = jnp.maximum(B - s.tgt[0], 0.0) + jnp.maximum(
            (jnp.max(p99) - s.tgt[1]) / jnp.maximum(s.tgt[1], mq.EPS), 0.0
        )
        above = jnp.where(pr > mq.H_UP, s.above + 1, 0)
        below = jnp.where(pr < mq.H_DOWN, s.below + 1, 0)
        degraded = avail < AVAIL_FULL
        up = (above >= mq.K_UP) | degraded
        down = (below >= mq.K_DOWN) & ~degraded
        d = jnp.where(
            up,
            jnp.minimum(s.d + 1, mq.D_MAX),
            jnp.where(down, jnp.maximum(s.d - 1, mq.D_MIN), s.d),
        )
        dl = jnp.where(
            up,
            jnp.maximum(s.delta_l - 1.0, mq.DL_MIN),
            jnp.where(
                down, jnp.minimum(s.delta_l + 1.0, mq.DL_MAX), s.delta_l
            ),
        )
        fm = jnp.where(
            up,
            jnp.minimum(s.f_max * 2.0, mq.F_HIGH),
            jnp.where(down, jnp.maximum(s.f_max * 0.5, mq.F_CAP), s.f_max),
        )
        return s._replace(
            L_hat=L_hat,
            p50=p50,
            p99=p99,
            d=d,
            delta_l=dl,
            delta_t=jnp.asarray(dep.rtt_ms, F) + 0.1 * dep.rtt_ms * jitter,
            f_max=fm,
            pressure=pr,
            above=jnp.where(up, 0, above),
            below=jnp.where(down, 0, below),
        )

    s = jax.lax.cond(t1 % dep.fast_ticks == 0, fast, lambda s: s, s)

    def slow(s):
        c = s.cache
        wf = c.win_writes / jnp.maximum(c.win_writes + c.win_reads, 1.0)
        return s._replace(
            cache=c._replace(
                write_frac=(1.0 - mq.BETA) * c.write_frac + mq.BETA * wf,
                win_writes=jnp.zeros_like(c.win_writes),
                win_reads=jnp.zeros_like(c.win_reads),
            )
        )

    s = jax.lax.cond(t1 % dep.slow_ticks == 0, slow, lambda s: s, s)

    mu = jnp.mean(L)
    ok = mu > 1e-9
    cv = jnp.where(ok, jnp.std(L) / jnp.where(ok, mu, 1.0), 0.0)
    acc = mq.Acc(
        queue_sum=acc.queue_sum + L,
        queue_max=jnp.maximum(acc.queue_max, jnp.max(L)),
        cv_sum=acc.cv_sum + cv,
        cv_count=acc.cv_count + ok.astype(F),
        queue_hist=mq._hist(acc.queue_hist, L, jnp.ones_like(L), edges),
        lat_hist=mq._hist(acc.lat_hist, lat, arrivals, edges),
        arrivals=acc.arrivals + jnp.sum(arrivals),
        steered=acc.steered + steered,
        eligible=acc.eligible + eligible,
        cache_hits=acc.cache_hits + hits,
    )
    return s, acc, (s.d, s.delta_l, s.f_max, s.pressure, mu), dropped


@functools.partial(jax.jit, static_argnums=(0, 1))
def _run(dep: Deployment, F, seed, targets, moved, xs):
    """One grid cell: scan the ticks; summary accumulators, per-tick
    knobs and the live entries the remap dropped."""
    T, R = xs[0].shape
    slots = jnp.asarray(mq.wave_slots(dep, R))
    edges = jnp.asarray(mq.HIST_EDGES, F)
    s, acc = mq._init(dep, seed, targets, F)

    def step(carry, x):
        s, acc, dropped = carry
        s, acc, knobs, n = _tick(dep, slots, edges, moved, s, acc, x)
        return (s, acc, dropped + n), knobs

    (_, acc, dropped), ys = jax.lax.scan(
        step,
        (s, acc, jnp.zeros((), F)),
        (jnp.arange(T, dtype=jnp.int32),) + tuple(xs),
    )
    return acc, ys, dropped


def simulate(
    dep: Deployment,
    grid,
    seed: int,
    targets: Tuple[float, float],
    dtype=jnp.float32,
) -> Dict[str, np.ndarray]:
    """The summary row of one (grid, seed) cell under the fault program:
    every field as float64 numpy, keyed by the fields of :data:`FIELDS`."""
    keys, mask, is_write = (np.asarray(a) for a in grid)
    sch, flip, moved = _program(dep, keys.shape[0])
    keys, mask, is_write = storm_overlay(sch.storm, keys, mask, is_write)
    if moved.shape[0]:
        feas = np.stack([
            live_feasible(dep, keys, mk, sch.scan_width) for mk in sch.masks
        ])[sch.epoch, np.arange(keys.shape[0])]
    else:
        feas = mq.feasible(dep, keys)
    acc, ys, dropped = _run(
        dep, dtype, seed, jnp.asarray(targets, jnp.float32),
        jnp.asarray(moved),
        (keys, mask, is_write, feas, sch.member, sch.detected, sch.avail,
         flip),
    )
    vals = list(acc) + list(ys) + [dropped]
    return {
        f: np.asarray(jnp.asarray(v, jnp.float32), np.float64)
        for (f, _), v in zip(FIELDS, vals)
    }
