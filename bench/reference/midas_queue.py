"""Plain reference of the MIDAS queue-network simulator: the reference
module ``midas_queue`` (a configuration names it under ``"reference"``).

An implementation of the simulator's semantics written from the paper's
algorithm and the deployment's stated settings, independent of the code
under test: it imports nothing of the program and takes nothing the
program made.  It covers what the benchmark's cells run:

* the consistent-hash ring (V virtual nodes per server) and the
  namespace-feasible set F(r) of the first ``d_max`` distinct servers
  clockwise of a key;
* the lease-mode cooperative cache, alone (``cache``) or as ``P``
  proxies whose views of remote installs and invalidations lag by the
  gossip delay (``fleet_cache``);
* routing in waves: ``hash`` (ring primary), ``power_of_d`` (JSQ(d) in
  F(r)) and ``midas`` (margined power-of-d with pins and an exact
  sliding-window leaky bucket), either ``n_groups`` waves that see the
  proxies' own sends, or one wave per proxy from its own staggered view;
* constant-rate FIFO servers, the EWMA telemetry and latency sketch,
  and the hysteresis controller on the paper's fast and slow cadences;
* the section III-B warmup that sets the control targets.

Random draws follow the simulator's stated RNG protocol (per tick
``split(rng, 3)`` into state, middleware and routing keys; per wave
``fold_in(route_key, wave)``), so one seed names one run and the
reference's rows are comparable with the program's row for row.

``dtype`` sets the precision of every float the simulation computes:
float32 is the precision the deployment states; bfloat16 is the
lower-precision control that the comparison must reject.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Paper section IV-E cadences and control constants.
T_FAST_MS = 250.0
T_SLOW_MS = 30_000.0
W_WINDOW_MS = 1000.0
PIN_MS = 300.0
ALPHA = 0.2
EPS = 1e-6
D_INIT, D_MIN, D_MAX = 2, 1, 4
DL_INIT, DL_MIN, DL_MAX = 4.0, 2.0, 8.0
F_CAP, F_HIGH = 0.10, 1.0
H_DOWN, H_UP = 0.02, 0.10
K_UP, K_DOWN = 3, 8
# Cooperative cache (paper section IV-C).
BETA = 0.1
W_HIGH = 0.3
GUARD_MIN_EVENTS = 64.0
# Latency sketch depth and the log-spaced summary histogram.
SKETCH_K = 64
RING_SCAN = 16
HIST_EDGES = np.geomspace(1e-2, 1e6, 513)
# The warmup: light traffic for 1200 ticks from a fixed seed.
WARMUP_T = 1200
WARMUP_SEED = 99

# the configuration's settings this reference takes as they are stated
SIM_KEYS = (
    "m", "P", "N", "V", "dt_ms", "service_ms", "d_max", "rtt_ms",
    "n_groups", "lease_ms", "gossip_ms", "fleet_routing",
)
# settings it implements one value of
FIXED = {"cache_mode": "lease", "consensus": "mean"}
POLICIES = ("hash", "power_of_d", "midas")
MIDDLEWARE = ((), ("cache",), ("fleet_cache",))
CONTROLLERS = ("hysteresis",)


@dataclasses.dataclass(frozen=True)
class Deployment:
    """The simulated deployment, as a configuration file states it."""

    m: int
    P: int
    N: int
    V: int
    dt_ms: float
    service_ms: float
    d_max: int
    rtt_ms: float
    n_groups: int
    lease_ms: float
    gossip_ms: float
    fleet_routing: bool
    fixed_d: int
    policy: str
    middleware: Tuple[str, ...]

    @property
    def fast_ticks(self) -> int:
        return max(int(round(T_FAST_MS / self.dt_ms)), 1)

    @property
    def slow_ticks(self) -> int:
        return max(int(round(T_SLOW_MS / self.dt_ms)), 1)

    @property
    def bucket_slots(self) -> int:
        return max(int(round(W_WINDOW_MS / self.dt_ms)), 1)

    @property
    def gossip_depth(self) -> int:
        return max(int(math.ceil(self.gossip_ms / self.dt_ms)), 1)

    @property
    def waves(self) -> int:
        return self.P if self.fleet_routing else self.n_groups


def deployment(sim: Dict, traffic: Dict) -> Deployment:
    """The deployment a configuration's ``sim`` settings and a traffic
    file's policy stack state; a ``ValueError`` names every key and
    value this reference does not implement."""
    bad = [f"sim.{k} (missing)" for k in SIM_KEYS if k not in sim]
    for k, v in sim.items():
        if k in FIXED and v != FIXED[k]:
            bad.append(f"sim.{k}={v!r} (only {FIXED[k]!r})")
        elif k not in FIXED and k not in SIM_KEYS:
            bad.append(f"sim.{k}={v!r}")
    policy = traffic["policy"]
    middleware = tuple(traffic["middleware"])
    controller = traffic.get("controller", "hysteresis")
    if policy not in POLICIES:
        bad.append(f"policy={policy!r}")
    if middleware not in MIDDLEWARE:
        bad.append(f"middleware={list(middleware)!r}")
    if controller not in CONTROLLERS:
        bad.append(f"controller={controller!r}")
    if bad:
        raise ValueError(
            "reference midas_queue does not implement " + ", ".join(bad)
        )
    return Deployment(
        **{k: sim[k] for k in SIM_KEYS},
        fixed_d=int(traffic.get("fixed_d", 2)),
        policy=policy,
        middleware=middleware,
    )


# ---------------------------------------------------------------------------
# Ring and feasible sets (host, numpy)
# ---------------------------------------------------------------------------


def _mix(x):
    x = np.asarray(x, np.uint32).copy()
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


def _hash2(a, b):
    a = np.asarray(a, np.uint32)
    b = np.asarray(b, np.uint32)
    return _mix(
        a ^ (_mix(b) + np.uint32(0x9E3779B9) + (a << np.uint32(6))
             + (a >> np.uint32(2)))
    )


def ring(m: int, V: int):
    """Sorted virtual-node positions and their owning servers."""
    servers = np.repeat(np.arange(m, dtype=np.uint32), V)
    replicas = np.tile(np.arange(V, dtype=np.uint32), m)
    pos = _hash2(servers * np.uint32(0x10001) + replicas, 1)
    order = np.argsort(pos, kind="stable")
    return pos[order], servers[order].astype(np.int64)


def feasible(dep: Deployment, keys: np.ndarray) -> np.ndarray:
    """F(r) for every key: the first ``d_max`` distinct owners among the
    16 ring slots clockwise of the key, padded with (primary + i) mod m
    when fewer appear.  Slot 0 is the primary."""
    pos, owners = ring(dep.m, dep.V)
    n = pos.size
    kp = _hash2(np.asarray(keys, np.uint32), 7919)
    base = np.searchsorted(pos, kp) % n
    cand = owners[(base[..., None] + np.arange(RING_SCAN)) % n]
    fresh = np.stack(
        [
            np.all(cand[..., :j] != cand[..., j : j + 1], axis=-1)
            for j in range(RING_SCAN)
        ],
        axis=-1,
    )
    rank = np.cumsum(fresh, axis=-1) - 1
    out = np.full(keys.shape + (dep.d_max,), -1, np.int64)
    for r in range(dep.d_max):
        sel = fresh & (rank == r)
        val = np.take_along_axis(cand, sel.argmax(-1)[..., None], -1)
        out[..., r] = np.where(sel.any(-1), val[..., 0], -1)
    pad = (out[..., :1] + np.arange(dep.d_max)) % dep.m
    return np.where(out < 0, pad, out).astype(np.int32)


def wave_slots(dep: Deployment, R: int) -> np.ndarray:
    """(G, R/G) request-slot indices of each routing wave: contiguous
    blocks, or with fleet routing the slots r = g (mod P) of proxy g."""
    G = dep.waves
    if R % G:
        raise ValueError(f"R={R} is not a multiple of {G} waves")
    idx = np.arange(R)
    if dep.fleet_routing:
        return idx.reshape(R // G, G).T.copy()
    return idx.reshape(G, R // G)


# ---------------------------------------------------------------------------
# One tick
# ---------------------------------------------------------------------------


class Cache(NamedTuple):
    expiry: jnp.ndarray  # (N,) absolute lease expiry (ms)
    version: jnp.ndarray  # (N,) int32 version stored at install (-1 none)
    gversion: jnp.ndarray  # (N,) int32 authoritative version
    write_frac: jnp.ndarray  # () slow-loop EWMA of the write mix
    win_writes: jnp.ndarray  # () writes this slow window
    win_reads: jnp.ndarray  # () reads this slow window


class Gossip(NamedTuple):
    last_ms: jnp.ndarray  # (N,) time of the last install/invalidation
    origin: jnp.ndarray  # (N,) int32 proxy that made it
    lag_expiry: jnp.ndarray  # (D, N) converged table D ticks ago
    lag_version: jnp.ndarray  # (D, N)


class State(NamedTuple):
    L: jnp.ndarray  # (m,) queue lengths
    L_hat: jnp.ndarray  # (m,) EWMA view
    L_hat_p: jnp.ndarray  # (P, m) per-proxy views
    p50: jnp.ndarray  # (m,)
    p99: jnp.ndarray  # (m,)
    sk_buf: jnp.ndarray  # (m, K) recent latency observations
    sk_n: jnp.ndarray  # () int32 observations so far
    pin_server: jnp.ndarray  # (N,) int32
    pin_expiry: jnp.ndarray  # (N,)
    steer_hist: jnp.ndarray  # (W,) steered per routing call
    elig_hist: jnp.ndarray  # (W,) eligible per routing call
    hist_idx: jnp.ndarray  # () int32 routing calls so far
    d: jnp.ndarray  # () int32 knob
    delta_l: jnp.ndarray
    delta_t: jnp.ndarray
    f_max: jnp.ndarray
    pressure: jnp.ndarray
    above: jnp.ndarray  # () int32 consecutive ticks over H_UP
    below: jnp.ndarray  # () int32 consecutive ticks under H_DOWN
    cache: Cache
    gossip: Gossip
    tgt: jnp.ndarray  # (2,) imbalance and p99 targets
    rng: jnp.ndarray


class Acc(NamedTuple):
    queue_sum: jnp.ndarray
    queue_max: jnp.ndarray
    cv_sum: jnp.ndarray
    cv_count: jnp.ndarray
    queue_hist: jnp.ndarray
    lat_hist: jnp.ndarray
    arrivals: jnp.ndarray
    steered: jnp.ndarray
    eligible: jnp.ndarray
    cache_hits: jnp.ndarray


def _ewma(prev, x):
    return (1.0 - ALPHA) * prev + ALPHA * x


def _imbalance(x):
    return jnp.std(x) / (jnp.mean(x) + EPS)


def _quantiles(buf, n):
    """p50, p99 of each server's valid window (linear interpolation)."""
    K = buf.shape[1]
    n = jnp.minimum(n, K)
    srt = jnp.sort(jnp.where(jnp.arange(K)[None, :] < n, buf, jnp.inf), 1)
    nn = jnp.maximum(n, 1)

    def at(pos):
        lo = jnp.floor(pos).astype(jnp.int32)
        hi = jnp.minimum(lo + 1, nn - 1).astype(jnp.int32)
        w = (pos - lo).astype(buf.dtype)
        return (1 - w) * srt[:, lo] + w * srt[:, hi]

    i50 = jnp.clip((nn - 1) / 2, 0, K - 1).astype(jnp.float32)
    i99 = jnp.clip(
        jnp.ceil(0.99 * (nn.astype(jnp.float32) - 1)), 0, K - 1
    ).astype(jnp.float32)
    zero = jnp.zeros((), buf.dtype)
    return (
        jnp.where(n > 0, at(i50), zero),
        jnp.where(n > 0, at(i99), zero),
    )


def _hist(counts, values, weights, edges):
    b = jnp.searchsorted(edges, values.reshape(-1), side="right")
    return counts.at[b].add(weights.reshape(-1).astype(counts.dtype))


def _sampled(rng, R, d_max, d):
    """Slot 0 always, plus d-1 of slots 1..d_max-1 uniformly."""
    scores = jax.random.uniform(rng, (R, d_max)).at[:, 0].set(-1.0)
    rank = jnp.argsort(jnp.argsort(scores, axis=1), axis=1)
    return rank < d


def _cache_serve(dep, c, keys, mask, is_write, now, exp_view, ver_view):
    """Lease-mode cache tick against per-request views of the table:
    returns the new converged table, read hits, and the invalidation
    and install keys (sentinel N where none)."""
    N = dep.N
    valid = mask & ~is_write
    hit = valid & (exp_view > now) & (ver_view >= 0)
    w = is_write & mask
    wk = jnp.where(w, keys, N)
    gv = c.gversion.at[wk].add(1, mode="drop")
    expiry = c.expiry.at[wk].set(0.0, mode="drop")
    n = c.win_writes + c.win_reads
    live = jnp.where(
        n >= GUARD_MIN_EVENTS, c.win_writes / jnp.maximum(n, 1.0), 0.0
    )
    bypass = jnp.maximum(c.write_frac, live) > W_HIGH
    install = valid & ~hit & ~bypass
    ik = jnp.where(install, keys, N)
    expiry = expiry.at[ik].set(now + dep.lease_ms, mode="drop")
    version = c.version.at[ik].set(
        gv[jnp.minimum(ik, N - 1)], mode="drop"
    )
    c = c._replace(
        expiry=expiry,
        version=version,
        gversion=gv,
        win_writes=c.win_writes + jnp.sum(w),
        win_reads=c.win_reads + jnp.sum(valid),
    )
    return c, hit, wk, ik


def _route_wave(dep, s, rng, keys, mask, feas, L_view, now):
    """Route one wave; returns (state, assign, steered, eligible)."""
    F = L_view.dtype
    R = keys.shape[0]
    primary = feas[:, 0]
    Lf = L_view[feas]
    if dep.policy == "hash":
        z = jnp.zeros((), F)
        return s, jnp.where(mask, primary, -1), z, z
    if dep.policy == "power_of_d":
        sampled = _sampled(rng, R, dep.d_max, dep.fixed_d)
        tie = (
            jax.random.uniform(jax.random.fold_in(rng, 1), feas.shape)
            * 1e-3
        ).astype(F)
        best = jnp.argmin(jnp.where(sampled, Lf, jnp.inf) + tie, axis=1)
        assign = jnp.take_along_axis(feas, best[:, None], 1)[:, 0]
        z = jnp.zeros((), F)
        return s, jnp.where(mask, assign, -1), z, z
    # midas: candidates beyond the primary that clear both margins
    sampled = _sampled(rng, R, dep.d_max, s.d).at[:, 0].set(False)
    tie = (
        jax.random.uniform(jax.random.fold_in(rng, 2), feas.shape) * 1e-3
    ).astype(F)
    p50f = s.p50[feas]
    ok = (
        sampled
        & (Lf <= L_view[primary][:, None] - s.delta_l)
        & (p50f <= s.p50[primary][:, None] - s.delta_t)
    )
    slot = jnp.argmin(jnp.where(ok, Lf, jnp.inf) + tie, axis=1)
    best = jnp.take_along_axis(feas, slot[:, None], 1)[:, 0]
    candidate = jnp.any(ok, axis=1) & mask
    pinned = (
        (s.pin_expiry[keys] > now) & (s.pin_server[keys] >= 0) & mask
    )
    # exact sliding-window leaky bucket over the last W routing calls
    W = s.steer_hist.shape[0]
    i = s.hist_idx % W
    want = candidate & ~pinned
    elig_now = jnp.sum(want)
    elig_win = jnp.sum(s.elig_hist) - s.elig_hist[i] + elig_now
    steer_win = jnp.sum(s.steer_hist) - s.steer_hist[i]
    budget = jnp.floor(s.f_max * elig_win) - steer_win
    allowed = want & (jnp.cumsum(want.astype(jnp.int32)) - 1 < budget)
    assign = jnp.where(
        pinned, s.pin_server[keys], jnp.where(allowed, best, primary)
    )
    sk = jnp.where(allowed, keys, dep.N)
    n_allowed = jnp.sum(allowed).astype(F)
    s = s._replace(
        pin_server=s.pin_server.at[sk].set(best, mode="drop"),
        pin_expiry=s.pin_expiry.at[sk].set(now + PIN_MS, mode="drop"),
        steer_hist=s.steer_hist.at[i].set(n_allowed),
        elig_hist=s.elig_hist.at[i].set(elig_now.astype(F)),
        hist_idx=s.hist_idx + 1,
    )
    return s, jnp.where(mask, assign, -1), n_allowed, elig_now.astype(F)


def _tick(dep: Deployment, slots, edges, s: State, acc: Acc, xs):
    """One tick: middleware, routing waves, service, telemetry and the
    control loops; returns the state, the accumulators and this tick's
    knobs, mean queue and (m,) queue, arrivals and latency."""
    t, keys, mask, is_write, feas = xs
    F = s.L.dtype
    now = t.astype(F) * dep.dt_ms
    rng, _, r_route = jax.random.split(s.rng, 3)
    s = s._replace(rng=rng)

    # middleware: read hits are served at the proxy
    hits = jnp.zeros((), F)
    if dep.middleware == ("cache",):
        c = s.cache
        cache, hit, _, _ = _cache_serve(
            dep, c, keys, mask, is_write, now, c.expiry[keys],
            c.version[keys],
        )
        s = s._replace(cache=cache)
        mask = mask & ~hit
        hits = jnp.sum(hit).astype(F)
    elif dep.middleware == ("fleet_cache",):
        c, g = s.cache, s.gossip
        R = keys.shape[0]
        proxy = (jnp.arange(R, dtype=jnp.int32) + t) % dep.P
        D = g.lag_expiry.shape[0]
        lag = t % D
        fresh = (g.origin[keys] == proxy) | (
            now - g.last_ms[keys] >= dep.gossip_ms
        )
        exp_view = jnp.where(fresh, c.expiry[keys], g.lag_expiry[lag][keys])
        ver_view = jnp.where(
            fresh, c.version[keys], g.lag_version[lag][keys]
        )
        cache, hit, inv, ins = _cache_serve(
            dep, c, keys, mask, is_write, now, exp_view, ver_view
        )
        last_ms = g.last_ms.at[inv].set(now, mode="drop")
        origin = g.origin.at[inv].set(proxy, mode="drop")
        gossip = Gossip(
            last_ms=last_ms.at[ins].set(now, mode="drop"),
            origin=origin.at[ins].set(proxy, mode="drop"),
            lag_expiry=g.lag_expiry.at[lag].set(cache.expiry),
            lag_version=g.lag_version.at[lag].set(cache.version),
        )
        s = s._replace(cache=cache, gossip=gossip)
        mask = mask & ~hit
        hits = jnp.sum(hit).astype(F)

    # routing waves
    def wave(carry, wx):
        s, sent, steered, eligible = carry
        g, idx = wx
        if dep.fleet_routing:
            view = s.L_hat_p[(g + t) % dep.P]
        else:
            view = s.L_hat + sent
        s, assign, st, el = _route_wave(
            dep, s, jax.random.fold_in(r_route, g), keys[idx], mask[idx],
            feas[idx], view, now,
        )
        m_ok = mask[idx]
        sent = sent.at[jnp.where(m_ok, assign, 0)].add(
            jnp.where(m_ok, 1.0, 0.0).astype(F)
        )
        return (s, sent, steered + st, eligible + el), None

    z = jnp.zeros((), F)
    G = slots.shape[0]
    (s, arrivals, steered, eligible), _ = jax.lax.scan(
        wave,
        (s, jnp.zeros((dep.m,), F), z, z),
        (jnp.arange(G, dtype=jnp.int32), slots),
    )

    # constant-rate servers
    L = s.L + arrivals
    lat = (s.L + arrivals) * dep.service_ms
    L = L - jnp.minimum(L, dep.dt_ms / dep.service_ms)
    s = s._replace(L=L)
    t1 = t + 1
    K = s.sk_buf.shape[1]
    s = s._replace(
        sk_buf=s.sk_buf.at[:, s.sk_n % K].set(lat), sk_n=s.sk_n + 1
    )
    if dep.fleet_routing:
        phase = (jnp.arange(dep.P, dtype=jnp.int32) * dep.fast_ticks) // dep.P
        due = (t1 % dep.fast_ticks) == phase
        s = s._replace(
            L_hat_p=jnp.where(
                due[:, None], _ewma(s.L_hat_p, s.L[None, :]), s.L_hat_p
            )
        )

    def fast(s):
        p50_o, p99_o = _quantiles(s.sk_buf, s.sk_n)
        if dep.fleet_routing:
            L_hat = jnp.mean(s.L_hat_p, axis=0)
        else:
            L_hat = _ewma(s.L_hat, s.L)
        p50 = _ewma(s.p50, p50_o)
        p99 = _ewma(s.p99, p99_o)
        B = _imbalance(L_hat)
        jitter = jax.random.uniform(
            jax.random.fold_in(s.rng, 3), (), minval=-1.0, maxval=1.0
        ).astype(F)
        # hysteresis controller (Algorithm 1 lines 26-35)
        pr = jnp.maximum(B - s.tgt[0], 0.0) + jnp.maximum(
            (jnp.max(p99) - s.tgt[1]) / jnp.maximum(s.tgt[1], EPS), 0.0
        )
        above = jnp.where(pr > H_UP, s.above + 1, 0)
        below = jnp.where(pr < H_DOWN, s.below + 1, 0)
        up = above >= K_UP
        down = below >= K_DOWN
        d = jnp.where(
            up,
            jnp.minimum(s.d + 1, D_MAX),
            jnp.where(down, jnp.maximum(s.d - 1, D_MIN), s.d),
        )
        dl = jnp.where(
            up,
            jnp.maximum(s.delta_l - 1.0, DL_MIN),
            jnp.where(down, jnp.minimum(s.delta_l + 1.0, DL_MAX), s.delta_l),
        )
        fm = jnp.where(
            up,
            jnp.minimum(s.f_max * 2.0, F_HIGH),
            jnp.where(down, jnp.maximum(s.f_max * 0.5, F_CAP), s.f_max),
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
                write_frac=(1.0 - BETA) * c.write_frac + BETA * wf,
                win_writes=jnp.zeros_like(c.win_writes),
                win_reads=jnp.zeros_like(c.win_reads),
            )
        )

    if dep.middleware:
        s = jax.lax.cond(t1 % dep.slow_ticks == 0, slow, lambda s: s, s)

    mu = jnp.mean(L)
    ok = mu > 1e-9
    cv = jnp.where(ok, jnp.std(L) / jnp.where(ok, mu, 1.0), 0.0)
    acc = Acc(
        queue_sum=acc.queue_sum + L,
        queue_max=jnp.maximum(acc.queue_max, jnp.max(L)),
        cv_sum=acc.cv_sum + cv,
        cv_count=acc.cv_count + ok.astype(F),
        queue_hist=_hist(acc.queue_hist, L, jnp.ones_like(L), edges),
        lat_hist=_hist(acc.lat_hist, lat, arrivals, edges),
        arrivals=acc.arrivals + jnp.sum(arrivals),
        steered=acc.steered + steered,
        eligible=acc.eligible + eligible,
        cache_hits=acc.cache_hits + hits,
    )
    knobs = (s.d, s.delta_l, s.f_max, s.pressure, mu, L, arrivals, lat)
    return s, acc, knobs


def _init(dep: Deployment, seed, targets, F) -> Tuple[State, Acc]:
    N, m = dep.N, dep.m
    D = dep.gossip_depth
    z = jnp.zeros((), F)
    zi = jnp.zeros((), jnp.int32)
    s = State(
        L=jnp.zeros((m,), F),
        L_hat=jnp.zeros((m,), F),
        L_hat_p=jnp.zeros((dep.P, m), F),
        p50=jnp.zeros((m,), F),
        p99=jnp.zeros((m,), F),
        sk_buf=jnp.zeros((m, SKETCH_K), F),
        sk_n=zi,
        pin_server=jnp.full((N,), -1, jnp.int32),
        pin_expiry=jnp.zeros((N,), F),
        steer_hist=jnp.zeros((dep.bucket_slots,), F),
        elig_hist=jnp.zeros((dep.bucket_slots,), F),
        hist_idx=zi,
        d=jnp.asarray(D_INIT, jnp.int32),
        delta_l=jnp.asarray(DL_INIT, F),
        delta_t=jnp.asarray(dep.rtt_ms, F),
        f_max=jnp.asarray(F_CAP, F),
        pressure=z,
        above=zi,
        below=zi,
        cache=Cache(
            expiry=jnp.zeros((N,), F),
            version=jnp.full((N,), -1, jnp.int32),
            gversion=jnp.zeros((N,), jnp.int32),
            write_frac=z,
            win_writes=z,
            win_reads=z,
        ),
        gossip=Gossip(
            last_ms=jnp.full((N,), -1e30, F),
            origin=jnp.full((N,), -1, jnp.int32),
            lag_expiry=jnp.zeros((D, N), F),
            lag_version=jnp.full((D, N), -1, jnp.int32),
        ),
        tgt=jnp.asarray(targets).astype(F),
        rng=jax.random.PRNGKey(seed),
    )
    nb = HIST_EDGES.size + 1
    acc = Acc(
        queue_sum=jnp.zeros((m,), F),
        queue_max=z,
        cv_sum=z,
        cv_count=z,
        queue_hist=jnp.zeros((nb,), F),
        lat_hist=jnp.zeros((nb,), F),
        arrivals=z,
        steered=z,
        eligible=z,
        cache_hits=z,
    )
    return s, acc


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _run(dep: Deployment, F, full: bool, seed, targets, keys, mask,
         is_write, feas):
    """One grid cell: scan the ticks; summary accumulators and per-tick
    knobs (and, with ``full``, the (T, m) timelines)."""
    T, R = keys.shape
    slots = jnp.asarray(wave_slots(dep, R))
    edges = jnp.asarray(HIST_EDGES, F)
    s, acc = _init(dep, seed, targets, F)

    def step(carry, xs):
        s, acc = carry
        s, acc, knobs = _tick(dep, slots, edges, s, acc, xs)
        if not full:
            knobs = knobs[:5]
        return (s, acc), knobs

    (_, acc), ys = jax.lax.scan(
        step,
        (s, acc),
        (jnp.arange(T, dtype=jnp.int32), keys, mask, is_write, feas),
    )
    return acc, ys


def warmup_targets(dep: Deployment, light_grid) -> Tuple[float, float]:
    """Section III-B: run the deployment bare (ring-primary routing, no
    middleware) on light traffic, and set the imbalance target to the
    median smoothed imbalance + 0.05 and the latency target to 1.25x the
    arrival-weighted p99 latency (at least RTT + 2 ms)."""
    wdep = dataclasses.replace(dep, policy="hash", middleware=())
    keys, mask, is_write = (np.asarray(a) for a in light_grid)
    _, ys = _run(
        wdep, jnp.float32, True, 0, jnp.asarray([0.15, 500.0]),
        keys, mask, is_write, feasible(wdep, keys),
    )
    L, arrivals, lat = (np.asarray(y, np.float64) for y in ys[5:])
    L_hat = np.zeros_like(L)
    prev = np.zeros(L.shape[1])
    for t in range(L.shape[0]):
        prev = (1.0 - ALPHA) * prev + ALPHA * L[t]
        L_hat[t] = prev
    B = L_hat.std(axis=1) / (L_hat.mean(axis=1) + EPS)
    v, w = lat.reshape(-1), arrivals.reshape(-1)
    if w.sum() > 0:
        order = np.argsort(v, kind="stable")
        cum = np.cumsum(w[order]) / w.sum()
        p99 = v[order][min(int(np.searchsorted(cum, 0.99)), v.size - 1)]
    else:
        p99 = dep.service_ms
    return (
        float(np.median(B) + 0.05),
        float(max(1.25 * p99, dep.rtt_ms + 2.0)),
    )


def targets(dep: Deployment, traffic: Dict, gen) -> Tuple[float, float]:
    """The control targets: pinned by the traffic file, or, for the
    adaptive policy with the warmup on, the warmup's on light traffic
    from the cell's generator ``gen`` at the width the program's warmup
    takes (4 x capacity + 8 slots), else the defaults."""
    pinned = traffic.get("targets")
    if pinned is not None:
        return tuple(map(float, pinned))
    if traffic.get("warmup") and dep.policy == "midas":
        light = gen.make(
            "light",
            T=WARMUP_T,
            m=dep.m,
            seed=WARMUP_SEED,
            N=dep.N,
            R=int(4 * dep.m * dep.dt_ms / dep.service_ms) + 8,
            dt_ms=dep.dt_ms,
            service_ms=dep.service_ms,
        )
        return warmup_targets(dep, light)
    return 0.15, 5.0 * dep.service_ms


# (reference field, the program's SweepResult.row attribute)
FIELDS = (
    ("queue_sum", "queue_sum"),
    ("queue_max", "queue_max_v"),
    ("cv_sum", "cv_sum"),
    ("cv_count", "cv_count"),
    ("queue_hist", "queue_hist"),
    ("lat_hist", "lat_hist"),
    ("arrivals", "arrivals_total"),
    ("steered", "steered_total"),
    ("eligible", "eligible_total"),
    ("cache_hits", "cache_hits_total"),
    ("d", "d_timeline"),
    ("delta_l", "delta_l_timeline"),
    ("f_max", "f_max_timeline"),
    ("pressure", "pressure"),
    ("q_mean", "q_mean_timeline"),
)


def simulate(
    dep: Deployment,
    grid,
    seed: int,
    targets: Tuple[float, float],
    dtype=jnp.float32,
) -> Dict[str, np.ndarray]:
    """The summary row of one (grid, seed) cell: every field as float64
    numpy, keyed by the reference fields of :data:`FIELDS`."""
    keys, mask, is_write = (np.asarray(a) for a in grid)
    acc, ys = _run(
        dep, dtype, False, seed, jnp.asarray(targets, jnp.float32),
        keys, mask, is_write, feasible(dep, keys),
    )
    vals = list(acc) + list(ys)
    return {
        f: np.asarray(jnp.asarray(v, jnp.float32), np.float64)
        for (f, _), v in zip(FIELDS, vals)
    }
