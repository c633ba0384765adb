"""The benchmark's traffic generator ``gen`` (a traffic file names it
under ``"generator"``): seeded request grids, Poisson arrivals per tick,
zipf or uniform keys, bursts on rotating hot subsets, and the
combinators that compose them.

A copy of the program's generators that the cells use (``light``,
``skewed``, ``bursty``, the four combinators, and the ``rename_storm``,
``flash_crowd`` and ``job_startup`` scenarios), kept here so that a
change to the program's own workload package cannot move the yardstick.
``make(name, ...)`` returns a :class:`Grid` of ``(T, R)`` arrays: request
keys in ``[0, N)``, a validity mask (a prefix of each row) and a write
flag.

Rates are fractions of the aggregate service capacity
``cap = m * dt_ms / service_ms`` requests per tick.  Everything is a pure
function of its arguments, so one seed always gives the same grid.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp

_GOLDEN = 0x9E3779B9


class Grid(NamedTuple):
    keys: jnp.ndarray  # (T, R) int32 in [0, N)
    mask: jnp.ndarray  # (T, R) bool, a prefix of each row
    is_write: jnp.ndarray  # (T, R) bool, only where mask


@dataclasses.dataclass(frozen=True)
class Params:
    T: int
    m: int
    seed: int
    dt_ms: float
    service_ms: float
    N: int
    R: int
    write_frac: float

    @property
    def cap(self) -> float:
        return self.m * self.dt_ms / self.service_ms

    @property
    def sec(self) -> jnp.ndarray:
        return jnp.arange(self.T, dtype=jnp.float32) * self.dt_ms / 1000.0

    @property
    def rng(self):
        return jax.random.PRNGKey(self.seed)

    def make(self, name: str, **overrides) -> Grid:
        return GENERATORS[name](dataclasses.replace(self, **overrides))


def _mix32(x):
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _hash2(a, b):
    a = jnp.asarray(a, jnp.uint32)
    b = jnp.asarray(b, jnp.uint32)
    return _mix32(a ^ (_mix32(b) + jnp.uint32(_GOLDEN) + (a << 6) + (a >> 2)))


def _zipf_cdf(N: int, alpha: float):
    w = jnp.arange(1, N + 1, dtype=jnp.float32) ** (-alpha)
    return jnp.cumsum(w) / jnp.sum(w)


def _zipf_keys(key, shape, N: int, alpha: float):
    """Zipf(alpha) keys (alpha 0: uniform); rank -> id is hashed, so hot
    keys land on unrelated servers."""
    if alpha <= 0.0:
        return jax.random.randint(key, shape, 0, N, dtype=jnp.int32)
    u = jax.random.uniform(key, shape)
    ranks = jnp.searchsorted(_zipf_cdf(N, alpha), u).astype(jnp.int32)
    return (_hash2(ranks, 3) % jnp.uint32(N)).astype(jnp.int32)


def _hot_keys(key, shape, epoch, N, *, subset, alpha, salt):
    """Zipf keys over a small hot subset that moves every epoch."""
    u = jax.random.uniform(key, shape)
    ranks = jnp.searchsorted(_zipf_cdf(subset, alpha), u).astype(jnp.int32)
    epochs = epoch[:, None].astype(jnp.uint32)
    mixed = _hash2(
        ranks.astype(jnp.uint32) + jnp.uint32(subset) * epochs, salt
    )
    return (mixed % jnp.uint32(N)).astype(jnp.int32)


def _poisson_grid(key, rate, R, N, alpha, write_frac) -> Grid:
    T = rate.shape[0]
    k1, k2, k3 = jax.random.split(key, 3)
    counts = jnp.minimum(jax.random.poisson(k1, rate).astype(jnp.int32), R)
    mask = jnp.arange(R)[None, :] < counts[:, None]
    keys = _zipf_keys(k2, (T, R), N, alpha)
    is_write = jax.random.uniform(k3, (T, R)) < write_frac
    return Grid(keys, mask, is_write & mask)


# -- combinators ------------------------------------------------------------


def mix(a: Grid, b: Grid, p: float, *, seed: int) -> Grid:
    """Each (tick, slot) comes from ``b`` with probability ``p``."""
    sel = jax.random.uniform(jax.random.PRNGKey(seed), a.mask.shape) < p
    return Grid(*(jnp.where(sel, y, x) for x, y in zip(a, b)))


def concat(a: Grid, b: Grid) -> Grid:
    return Grid(*(jnp.concatenate([x, y], axis=0) for x, y in zip(a, b)))


def scale_rate(g: Grid, factor: float, *, seed: int) -> Grid:
    """Thin (factor < 1) or boost (factor > 1, the tick's own requests
    replicated cyclically into free slots, capped at R) the rate."""
    if factor == 1.0:
        return g
    R = g.mask.shape[1]
    if factor < 1.0:
        u = jax.random.uniform(jax.random.PRNGKey(seed), g.mask.shape)
        mask = g.mask & (u < factor)
        return Grid(g.keys, mask, g.is_write & mask)
    order = jnp.argsort(~g.mask, axis=1, stable=True)
    keys = jnp.take_along_axis(g.keys, order, axis=1)
    is_write = jnp.take_along_axis(g.is_write, order, axis=1)
    counts = g.mask.sum(axis=1)
    target = jnp.minimum(jnp.round(counts * factor), R).astype(jnp.int32)
    slot = jnp.arange(R)[None, :]
    src = slot % jnp.maximum(counts, 1)[:, None]
    mask = slot < target[:, None]
    return Grid(
        jnp.take_along_axis(keys, src, axis=1),
        mask,
        jnp.take_along_axis(is_write, src, axis=1) & mask,
    )


def shift_hotset(g: Grid, offset: int, N: int) -> Grid:
    keys = jnp.mod(g.keys + jnp.int32(offset), jnp.int32(N))
    return g._replace(keys=keys.astype(jnp.int32))


# -- generators ---------------------------------------------------------------


def light(p: Params) -> Grid:
    """Steady 40 % utilisation, uniform keys."""
    rate = jnp.full((p.T,), 0.40 * p.cap)
    return _poisson_grid(p.rng, rate, p.R, p.N, 0.0, p.write_frac)


def skewed(p: Params) -> Grid:
    """Steady 70 % utilisation under zipf(0.9) popularity."""
    rate = jnp.full((p.T,), 0.70 * p.cap)
    return _poisson_grid(p.rng, rate, p.R, p.N, 0.9, p.write_frac)


def bursty(p: Params) -> Grid:
    """30 % background plus a 2 s burst at 3x capacity every 20 s, each
    burst on its own 32-key hot set."""
    k1, k2, k3 = jax.random.split(p.rng, 3)
    period_s, dur_s = 20.0, 2.0
    phase = jax.random.uniform(k3, ()) * period_s
    in_burst = ((p.sec + phase) % period_s) < dur_s
    burst_idx = ((p.sec + phase) // period_s).astype(jnp.int32)
    rate = jnp.full((p.T,), 0.30 * p.cap) + jnp.where(
        in_burst, 3.0 * p.cap, 0.0
    )
    g = _poisson_grid(k1, rate, p.R, p.N, 0.0, p.write_frac)
    hot = _hot_keys(
        k2, g.keys.shape, burst_idx, p.N, subset=32, alpha=1.1, salt=11
    )
    return g._replace(keys=jnp.where(in_burst[:, None], hot, g.keys))


def _phases(*parts: Grid) -> Grid:
    return functools.reduce(concat, [g for g in parts if g.keys.shape[0]])


def job_startup(p: Params) -> Grid:
    """A job launch: a skewed crush at ~2x capacity for T/8 ticks, then
    light traffic."""
    t_start = min(max(p.T // 8, 1), p.T)
    crush = scale_rate(
        p.make("skewed", T=t_start, seed=p.seed + 101, write_frac=0.3),
        3.0,
        seed=p.seed + 1,
    )
    crush = shift_hotset(crush, p.N // 3, p.N)
    steady = p.make("light", T=p.T - t_start, seed=p.seed + 202)
    return _phases(crush, steady)


def rename_storm(p: Params) -> Grid:
    """A write-heavy skewed stream blended into light background reads."""
    background = p.make("light", seed=p.seed + 303)
    renames = scale_rate(
        p.make("skewed", seed=p.seed + 404, write_frac=0.85),
        1.3,
        seed=p.seed + 2,
    )
    return mix(background, renames, 0.7, seed=p.seed + 3)


def flash_crowd(p: Params) -> Grid:
    """Light traffic, a read-only crowd at ~2x capacity for T/3 ticks on
    one namespace region, then light traffic again."""
    t_pre = min(max(p.T // 4, 1), p.T)
    t_peak = min(max(p.T // 3, 1), p.T - t_pre)
    calm_a = p.make("light", T=t_pre, seed=p.seed + 505)
    crowd = scale_rate(
        p.make("skewed", T=t_peak, seed=p.seed + 606, write_frac=0.0),
        2.8,
        seed=p.seed + 4,
    )
    crowd = shift_hotset(crowd, 2 * p.N // 3, p.N)
    calm_b = p.make("light", T=p.T - t_pre - t_peak, seed=p.seed + 707)
    return _phases(calm_a, crowd, calm_b)


GENERATORS: Dict[str, Callable[[Params], Grid]] = {
    "light": light,
    "skewed": skewed,
    "bursty": bursty,
    "job_startup": job_startup,
    "rename_storm": rename_storm,
    "flash_crowd": flash_crowd,
}


def make(
    name: str,
    *,
    T: int,
    m: int,
    seed: int,
    N: int,
    R: int = 0,
    dt_ms: float = 50.0,
    service_ms: float = 100.0,
    write_frac: float = 0.05,
) -> Grid:
    """The ``(T, R)`` grid of generator ``name``; ``R`` 0 means
    ``4 * cap + 8`` slots per tick."""
    if name not in GENERATORS:
        raise ValueError(
            f"unknown traffic generator {name!r}; available: "
            f"{', '.join(sorted(GENERATORS))}"
        )
    cap = m * dt_ms / service_ms
    p = Params(
        T=T,
        m=m,
        seed=seed,
        dt_ms=dt_ms,
        service_ms=service_ms,
        N=N,
        R=R or int(4 * cap) + 8,
        write_frac=write_frac,
    )
    return GENERATORS[name](p)
