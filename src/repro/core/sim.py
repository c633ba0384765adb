"""Queue-network simulator for the MIDAS evaluation (paper §VI).

m metadata servers, each a FIFO queue with constant 100 ms service time
(the paper's stress bound).  Time advances in dt_ms ticks under
``jax.lax.scan``; each tick first runs the middleware pipeline (stages may
absorb requests at the proxy — the cooperative cache is the reference
stage), then routes the surviving batch with the policy resolved from the
registry (``repro.core.policies``), applies service, refreshes (delayed)
telemetry, and runs the fast/slow control loops on their paper cadences.

Within a tick, requests are processed in ``n_groups`` sequential waves:
every wave sees the stale EWMA telemetry *plus* the proxies' own
assignments from earlier waves (a proxy knows what it already sent), which
is the honest middle ground between full per-request sequencing and pure
batch routing.  The waves themselves run as an inner ``jax.lax.scan``
(DESIGN.md §9): the feasible-set gather is one batched
``hashring.feasible_set`` call per tick, per-wave RNG keys are pre-split,
and the policy state threads through the wave carry — so trace/HLO size
and compile time are O(1) in ``n_groups`` and ``P`` instead of O(G).
``SimConfig(unroll_waves=True)`` keeps the pre-scan Python-loop engine as
the bit-for-bit parity reference (tests) and the E10 "before" baseline.

``simulate`` runs one config; ``simulate_sweep`` batches seeds and
workload grids with nested ``jax.vmap`` (one compiled scan per policy)
and fans out across policies — the API the benchmark suite uses.  Its
``metrics="summary"`` mode carries O(m) streaming accumulators
(:class:`SummaryResult`) through the scan instead of stacking (T, m)
timelines, collapsing sweep memory from O(B·T·m) to O(B·m).
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Dict, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cache as cache_lib
from repro.core import controllers as ctrl_lib
from repro.core import faults as faults_lib
from repro.core import fleet as fleet_lib
from repro.core import hashring, telemetry
from repro.core import middleware as mw_lib
from repro.core import policies as policy_lib
from repro.core import registry as registry_lib
from repro.core.controllers.base import Knobs, Signals
from repro.core.policies.base import RouteContext, RouteStats
from repro.core.workloads import Workload
from repro.kernels import common as kernels_common
from repro.obs import trace as obs_trace

# JAX's compile events reach the flight recorder from here on
obs_trace.listen_compile()

# Snapshot of the registry at import time; prefer policies.available().
POLICIES = policy_lib.available()

METRICS_MODES = ("full", "summary")


@dataclasses.dataclass(frozen=True)
class SimConfig:
    m: int = 8  # metadata servers
    P: int = 8  # independent proxies (fleet size)
    N: int = 4096  # namespace size (keys)
    dt_ms: float = 50.0
    service_ms: float = 100.0  # paper: constant 100 ms per RPC
    policy: str = "midas"  # any name in policies.available()
    d_max: int = 4
    V: int = 64  # virtual nodes per server
    rtt_ms: float = 2.0
    n_groups: int = 8  # routing waves per tick
    middleware: Tuple[str, ...] = ()  # pipeline stages, applied in order
    cache_enabled: bool = False  # legacy alias for middleware=("cache",)
    cache_mode: str = "lease"  # lease | ttl_aggregate | ttl_per_key
    lease_ms: float = 5000.0
    p_star: float = 1e-4
    # fleet knobs (repro.core.fleet): gossip propagation delay for the
    # "fleet_cache" stage, and per-proxy routing (one wave per proxy, own
    # staggered telemetry view, no within-tick sharing across proxies —
    # replaces the n_groups waves when enabled)
    gossip_ms: float = 0.0
    fleet_routing: bool = False
    fixed_d: int = 2  # d for power_of_d policy
    # control plane: any name in controllers.available(), plus the §IV-E
    # ablation decorators and the fleet-consensus reducer feeding it
    controller: str = "hysteresis"
    consensus: str = "mean"  # mean | median | max (fleet view reducer)
    ablate: str = ""  # comma-joined subset of controllers.ABLATIONS
    # oscillation guard (controllers.guard): wrap the controller in the
    # limit-cycle circuit breaker.  False (default) is the identically-
    # untouched engine (golden contract).
    guard: bool = False
    # fault injection (repro.core.faults): tuple of registered fault
    # names and/or FaultEvent instances, compiled host-side into
    # time-indexed schedules riding the scan xs.  None and () are both
    # the identically-untouched zero-fault engine (golden contract).
    faults: Optional[Tuple] = None
    # reference engine: unroll the routing waves as a Python loop (the
    # pre-scan semantics, O(G) trace size) — parity tests and the E10
    # "before" baseline; production always uses the wave scan
    unroll_waves: bool = False
    # wave-routing implementation (DESIGN.md §15): "auto" resolves per
    # backend (Pallas iff TPU, REPRO_KERNEL_IMPL override), "ref" pins
    # the pure-jnp policy expressions (the golden-parity path on CPU),
    # "pallas" forces the midas_route.route_select kernel (interpret
    # mode off-TPU) — bit-for-bit with "ref" by contract.
    route_impl: str = "auto"
    seed: int = 0

    def __post_init__(self):
        """Eager validation: bad names/sizes fail at construction with the
        alternatives spelled out, not deep inside the jitted scan."""
        for name in ("m", "P", "N", "V", "n_groups", "d_max", "fixed_d"):
            v = getattr(self, name)
            if not isinstance(v, int) or isinstance(v, bool) or v <= 0:
                raise ValueError(
                    f"SimConfig.{name} must be a positive int, got {v!r}"
                )
        # registry / enum membership: all routed through the shared
        # repro.core.registry helpers, so every axis raises the same
        # "unknown <kind> ...; available: ..." text
        policy_lib.get_class(self.policy)
        for stage in self.middleware:
            registry_lib.validate_choice(
                stage, "middleware stage", mw_lib.available()
            )
        ctrl_lib.get_class(self.controller)
        registry_lib.validate_choice(
            self.consensus,
            "consensus reducer",
            telemetry.CONSENSUS_REDUCERS,
        )
        ctrl_lib.parse_ablations(self.ablate)  # raises on unknown tokens
        if not isinstance(self.guard, bool):
            raise ValueError(
                f"SimConfig.guard must be a bool, got {self.guard!r}"
            )
        registry_lib.validate_choice(
            self.cache_mode, "cache_mode", cache_lib.MODES
        )
        registry_lib.validate_choice(
            self.route_impl, "route_impl", kernels_common.ROUTE_IMPLS
        )
        if self.gossip_ms < 0:
            raise ValueError(
                f"SimConfig.gossip_ms must be >= 0, got {self.gossip_ms!r}"
            )
        if self.faults is not None:
            if not isinstance(self.faults, (tuple, list)):
                raise ValueError(
                    f"SimConfig.faults must be a tuple of fault names "
                    f"or FaultEvent, got {self.faults!r}"
                )
            # canonicalize eagerly (frozen dataclass): names become
            # default events, lists become tuples — keeps the config
            # hashable for jit static args and the fault compiler cache
            object.__setattr__(
                self, "faults", faults_lib.normalize(self.faults)
            )
            faults_lib.validate_events(self.faults, m=self.m, P=self.P)

    @property
    def fault_events(self) -> Tuple:
        """Canonical tuple of FaultEvent (empty when faults is None)."""
        return faults_lib.normalize(self.faults)

    @property
    def t_fast_ticks(self) -> int:
        return max(int(round(ctrl_lib.T_FAST_MS / self.dt_ms)), 1)

    @property
    def t_slow_ticks(self) -> int:
        return max(int(round(ctrl_lib.T_SLOW_MS / self.dt_ms)), 1)

    @property
    def w_ticks(self) -> int:
        return max(int(round(ctrl_lib.W_WINDOW_MS / self.dt_ms)), 1)

    @property
    def serve_per_tick(self) -> float:
        return self.dt_ms / self.service_ms

    @property
    def middleware_chain(self) -> Tuple[str, ...]:
        """Resolved pipeline: the legacy cache flag prepends the cache."""
        chain = tuple(self.middleware)
        if self.cache_enabled and "cache" not in chain:
            chain = ("cache",) + chain
        return chain


class SimState(NamedTuple):
    L: jnp.ndarray  # (m,) float32 queue length
    L_hat: jnp.ndarray  # (m,) float32 EWMA of observed L
    L_hat_p: jnp.ndarray  # (P, m) float32 per-proxy views (fleet)
    p50_hat: jnp.ndarray  # (m,) float32 EWMA p50 (ms)
    p99_hat: jnp.ndarray  # (m,) float32 EWMA p99 (ms)
    sketch: telemetry.LatencySketch
    policy: tuple  # policy-owned pytree (see policies.base)
    ctrl: ctrl_lib.ControlState  # knobs + targets + controller inner
    mw: tuple  # per-stage middleware pytrees, chain order
    win_writes: jnp.ndarray  # () float32 writes this T_slow window
    win_events: jnp.ndarray  # () float32 valid requests this window
    rng: jnp.ndarray


class TickOut(NamedTuple):
    L: jnp.ndarray  # (m,) queue snapshot after tick
    arrivals: jnp.ndarray  # (m,) arrivals routed this tick
    lat_pred: jnp.ndarray  # (m,) predicted latency of a new arrival (ms)
    d: jnp.ndarray  # () int32 control knob
    delta_l: jnp.ndarray  # ()
    f_max: jnp.ndarray  # () steering-bucket cap this tick
    pressure: jnp.ndarray  # ()
    steered: jnp.ndarray  # ()
    eligible: jnp.ndarray  # ()
    cache_hits: jnp.ndarray  # () requests absorbed by the pipeline
    dV: jnp.ndarray  # () potential change from steering this tick
    # () live cache entries the remap invalidation dropped this tick;
    # only under a fault program (None otherwise)
    remap_dropped: Optional[jnp.ndarray] = None


class SimResult(NamedTuple):
    queue_timeline: np.ndarray  # (T, m)
    arrivals: np.ndarray  # (T, m)
    lat_pred: np.ndarray  # (T, m)
    d_timeline: np.ndarray  # (T,)
    delta_l_timeline: np.ndarray
    pressure: np.ndarray  # (T,)
    steered: np.ndarray  # (T,)
    eligible: np.ndarray  # (T,)
    cache_hits: np.ndarray  # (T,)
    final_cache: Optional[object]
    config: SimConfig
    f_max_timeline: Optional[np.ndarray] = None  # (T,) bucket cap

    # ---- paper metrics -------------------------------------------------
    def mean_queue(self) -> float:
        return float(self.queue_timeline.mean())

    def max_queue(self) -> float:
        return float(self.queue_timeline.max())

    def worst_case_queue(self, q: float = 99.9) -> float:
        return float(np.percentile(self.queue_timeline, q))

    def dispersion(self) -> float:
        """CV of per-server time-averaged queue length (paper §VI-C)."""
        per_server = self.queue_timeline.mean(axis=0)
        mu = per_server.mean()
        if mu < 1e-9:
            return 0.0
        return float(per_server.std() / mu)

    def dispersion_t(self) -> float:
        """Time-average of instantaneous CV across servers."""
        mu = self.queue_timeline.mean(axis=1)
        sd = self.queue_timeline.std(axis=1)
        ok = mu > 1e-9
        if not ok.any():
            return 0.0
        return float((sd[ok] / mu[ok]).mean())

    def latency_quantiles(self, qs=(50, 99)) -> Tuple[float, ...]:
        """Arrival-weighted request latency quantiles (ms)."""
        return telemetry.weighted_quantiles(self.lat_pred, self.arrivals, qs)


# ---------------------------------------------------------------------------
# Streaming summary metrics (metrics="summary")
# ---------------------------------------------------------------------------


class KnobTrace(NamedTuple):
    """Per-tick control-plane scalars emitted as the summary scan's ys:
    O(T) total — knob trajectories survive ``metrics="summary"`` even
    though the O(T·m) queue timelines do not, so E4/E8/E9-style cells
    can report oscillation, settling, and churn (DESIGN.md §10).
    ``q_mean`` (the across-server mean queue per tick) rides along so
    the ``repro.obs.windows`` warmup/stable/cooldown detector has a
    steady-state series in BOTH metrics modes (DESIGN.md §13)."""

    d: jnp.ndarray  # (T,) int32
    delta_l: jnp.ndarray  # (T,) float32
    f_max: jnp.ndarray  # (T,) float32
    pressure: jnp.ndarray  # (T,) float32
    q_mean: jnp.ndarray  # (T,) float32 across-server mean queue


class SummaryAcc(NamedTuple):
    """O(m) accumulators carried through the tick scan instead of a
    stacked (T, m) ``TickOut`` timeline (DESIGN.md §9)."""

    n_ticks: jnp.ndarray  # () int32
    queue_sum: jnp.ndarray  # (m,) per-server queue-length sums
    queue_max: jnp.ndarray  # ()
    cv_sum: jnp.ndarray  # () sum of instantaneous CV over ok ticks
    cv_count: jnp.ndarray  # () number of ok ticks
    queue_hist: telemetry.HistSketch  # all (t, server) queue samples
    lat_hist: telemetry.HistSketch  # lat_pred weighted by arrivals
    arrivals: jnp.ndarray  # ()
    steered: jnp.ndarray  # ()
    eligible: jnp.ndarray  # ()
    cache_hits: jnp.ndarray  # ()
    remap_dropped: Optional[jnp.ndarray] = None  # () see TickOut


def _summary_init(m: int, faults: bool = False) -> SummaryAcc:
    """Zeroed accumulators; ``faults`` (a fault program is compiled)
    adds the remap-drop count."""
    z = jnp.zeros((), jnp.float32)
    return SummaryAcc(
        n_ticks=jnp.zeros((), jnp.int32),
        queue_sum=jnp.zeros((m,), jnp.float32),
        queue_max=z,
        cv_sum=z,
        cv_count=z,
        queue_hist=telemetry.make_hist(),
        lat_hist=telemetry.make_hist(),
        arrivals=z,
        steered=z,
        eligible=z,
        cache_hits=z,
        remap_dropped=z if faults else None,
    )


def _summary_update(acc: SummaryAcc, out: TickOut) -> SummaryAcc:
    L = out.L
    mu = jnp.mean(L)
    ok = mu > 1e-9
    cv = jnp.where(ok, jnp.std(L) / jnp.where(ok, mu, 1.0), 0.0)
    return SummaryAcc(
        n_ticks=acc.n_ticks + 1,
        queue_sum=acc.queue_sum + L,
        queue_max=jnp.maximum(acc.queue_max, jnp.max(L)),
        cv_sum=acc.cv_sum + cv,
        cv_count=acc.cv_count + ok.astype(jnp.float32),
        queue_hist=telemetry.hist_add(acc.queue_hist, L, jnp.ones_like(L)),
        lat_hist=telemetry.hist_add(acc.lat_hist, out.lat_pred, out.arrivals),
        arrivals=acc.arrivals + jnp.sum(out.arrivals),
        steered=acc.steered + out.steered,
        eligible=acc.eligible + out.eligible,
        cache_hits=acc.cache_hits + out.cache_hits,
        remap_dropped=(
            None
            if out.remap_dropped is None
            else acc.remap_dropped + out.remap_dropped
        ),
    )


@dataclasses.dataclass(frozen=True)
class SummaryResult:
    """Streaming summary of one (policy, workload, seed) run.

    Exposes the same paper-metric API as :class:`SimResult` so benchmark
    code is agnostic to ``metrics=``.  Mean / max / dispersion are exact
    up to fp accumulation order; worst-case and latency quantiles come
    from :class:`telemetry.HistSketch` (bin-resolution approximations).
    The parity contract — a summary row equals :func:`summarize` of the
    corresponding full-timeline row — is tested in tests/test_engine.py.
    """

    n_ticks: int
    queue_sum: np.ndarray  # (m,)
    queue_max_v: float
    cv_sum: float
    cv_count: float
    queue_hist: np.ndarray  # (HIST_BINS + 2,)
    lat_hist: np.ndarray  # (HIST_BINS + 2,)
    arrivals_total: float
    steered_total: float
    eligible_total: float
    cache_hits_total: float
    config: SimConfig
    # control-plane trajectories (KnobTrace ys): O(T) scalars per tick,
    # kept even in summary mode so cells can report control behaviour
    d_timeline: Optional[np.ndarray] = None  # (T,)
    delta_l_timeline: Optional[np.ndarray] = None  # (T,)
    f_max_timeline: Optional[np.ndarray] = None  # (T,)
    pressure: Optional[np.ndarray] = None  # (T,)
    q_mean_timeline: Optional[np.ndarray] = None  # (T,) mean queue
    # cache entries the remap invalidation dropped while still live, over
    # the horizon; only under a fault program
    remap_dropped_total: Optional[float] = None

    # ---- paper metrics (SimResult-compatible) --------------------------
    def mean_queue(self) -> float:
        n = max(self.n_ticks * self.queue_sum.shape[0], 1)
        return float(self.queue_sum.sum() / n)

    def max_queue(self) -> float:
        return float(self.queue_max_v)

    def worst_case_queue(self, q: float = 99.9) -> float:
        return telemetry.hist_quantile(self.queue_hist, q)

    def dispersion(self) -> float:
        """CV of per-server time-averaged queue length (paper §VI-C)."""
        per_server = self.queue_sum / max(self.n_ticks, 1)
        mu = per_server.mean()
        if mu < 1e-9:
            return 0.0
        return float(per_server.std() / mu)

    def dispersion_t(self) -> float:
        """Time-average of instantaneous CV across servers."""
        if self.cv_count <= 0:
            return 0.0
        return float(self.cv_sum / self.cv_count)

    def latency_quantiles(self, qs=(50, 99)) -> Tuple[float, ...]:
        """Arrival-weighted latency quantiles (ms), sketch resolution."""
        return tuple(telemetry.hist_quantile(self.lat_hist, q) for q in qs)


def _to_summary(
    cfg: SimConfig, acc: SummaryAcc, trace: Optional[KnobTrace] = None
) -> SummaryResult:
    """Host-side SummaryResult from a (device or host) SummaryAcc."""
    return SummaryResult(
        n_ticks=int(acc.n_ticks),
        queue_sum=np.asarray(acc.queue_sum),
        queue_max_v=float(acc.queue_max),
        cv_sum=float(acc.cv_sum),
        cv_count=float(acc.cv_count),
        queue_hist=np.asarray(acc.queue_hist.counts),
        lat_hist=np.asarray(acc.lat_hist.counts),
        arrivals_total=float(acc.arrivals),
        steered_total=float(acc.steered),
        eligible_total=float(acc.eligible),
        cache_hits_total=float(acc.cache_hits),
        config=cfg,
        d_timeline=None if trace is None else np.asarray(trace.d),
        delta_l_timeline=(
            None if trace is None else np.asarray(trace.delta_l)
        ),
        f_max_timeline=None if trace is None else np.asarray(trace.f_max),
        pressure=None if trace is None else np.asarray(trace.pressure),
        q_mean_timeline=(
            None if trace is None else np.asarray(trace.q_mean)
        ),
        remap_dropped_total=(
            None if acc.remap_dropped is None else float(acc.remap_dropped)
        ),
    )


@functools.partial(jax.jit, static_argnums=(0,))
def _reduce_ticks(m: int, outs: TickOut) -> SummaryAcc:
    """Fold a stacked (T, ...) TickOut through the summary accumulators —
    the same per-tick updates the streaming mode applies in-scan."""

    def step(acc, out):
        return _summary_update(acc, out), None

    acc, _ = jax.lax.scan(step, _summary_init(m), outs)
    return acc


def summarize(result: SimResult) -> SummaryResult:
    """Post-hoc reduction of a full-timeline result through the SAME
    streaming accumulators as ``metrics="summary"`` — the reference side
    of the summary parity contract (tests/test_engine.py)."""
    T, m = result.queue_timeline.shape
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    zeros = jnp.zeros((T,), jnp.float32)
    outs = TickOut(
        L=f32(result.queue_timeline),
        arrivals=f32(result.arrivals),
        lat_pred=f32(result.lat_pred),
        d=jnp.zeros((T,), jnp.int32),
        delta_l=zeros,
        f_max=zeros,
        pressure=zeros,
        steered=f32(result.steered),
        eligible=f32(result.eligible),
        cache_hits=f32(result.cache_hits),
        dV=zeros,
    )
    f_max_tl = (
        np.zeros_like(np.asarray(result.d_timeline, np.float32))
        if result.f_max_timeline is None
        else np.asarray(result.f_max_timeline)
    )
    trace = KnobTrace(
        d=np.asarray(result.d_timeline),
        delta_l=np.asarray(result.delta_l_timeline),
        f_max=f_max_tl,
        pressure=np.asarray(result.pressure),
        # same jnp float32 mean as the in-scan ys — keeps the summary
        # parity contract bitwise, not merely approximate
        q_mean=np.asarray(jnp.mean(outs.L, axis=1)),
    )
    return _to_summary(
        result.config, jax.device_get(_reduce_ticks(m, outs)), trace
    )


# ---------------------------------------------------------------------------
# The tick: middleware pipeline -> wave-scanned routing -> dynamics
# ---------------------------------------------------------------------------


def _middlewares(cfg: SimConfig) -> Tuple[mw_lib.Middleware, ...]:
    return tuple(mw_lib.get(name) for name in cfg.middleware_chain)


def _controller(cfg: SimConfig) -> ctrl_lib.Controller:
    """The configured controller, with the §IV-E ablation decorators
    (``cfg.ablate``) wrapped around its emitted knob view and the
    oscillation guard (``cfg.guard``) as the outermost decorator."""
    ctrl = ctrl_lib.wrap_ablations(ctrl_lib.get(cfg.controller), cfg.ablate)
    return ctrl_lib.wrap_guard(ctrl, cfg.guard)


def _wave_split(cfg: SimConfig, x):
    """Reshape a (..., R) batch into (..., G, R/G) routing waves.

    Legacy: G = n_groups contiguous waves.  Fleet: one wave per proxy —
    wave g holds slots r ≡ g (mod P), served by proxy (g + tick) % P to
    match fleet.proxy_assign.  Works on one tick's (R,) vector or a whole
    (T, R) grid — the scan engine hoists the key split (and the feasible
    gather on it) out of the tick loop entirely.
    """
    R = x.shape[-1]
    G = cfg.P if cfg.fleet_routing else cfg.n_groups
    pad = (-R) % G
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    if cfg.fleet_routing:
        xg = xp.reshape(xp.shape[:-1] + (-1, G))
        return jnp.swapaxes(xg, -1, -2)
    return xp.reshape(xp.shape[:-1] + (G, -1))


def _wave_counts(m: int, mask, assign) -> jnp.ndarray:
    """(m,) routed-arrival counts of one wave (masked scatter-add)."""
    sink = jnp.where(mask, assign, 0)
    return jnp.zeros((m,), jnp.float32).at[sink].add(
        jnp.where(mask, 1.0, 0.0)
    )


# Trace counter for the wave-scan body: increments once per (re)trace of
# the body — NOT once per wave — letting tests assert that trace size
# stays O(1) in n_groups/P (the unrolled reference executes its loop body
# G times per trace instead).
_WAVE_TRACES = [0]


def _route_waves_scan(
    cfg: SimConfig,
    ring: hashring.Ring,
    policy: policy_lib.Policy,
    state: SimState,
    knobs: Knobs,
    t,
    now_ms,
    r_route,
    keysg,
    maskg,
    feasg,
):
    """Route a tick's G waves as one ``jax.lax.scan`` over waves.

    Hoisted out of the wave loop: the feasible sets (ONE batched
    ``hashring.feasible_set`` gather over the whole horizon, riding the
    tick scan's inputs), the per-wave RNG keys (vmapped fold_in —
    bitwise identical to the unrolled engine's per-wave fold_in), and,
    in fleet mode, the wave-rotation gather of per-proxy telemetry views
    (fleet.wave_views).  The wave carry threads the policy state, the
    within-tick own-sends accumulator, and the RouteStats sum.
    """
    G = keysg.shape[0]
    rngs = jax.vmap(lambda g: jax.random.fold_in(r_route, g))(jnp.arange(G))
    impl = kernels_common.resolve_route_impl(cfg.route_impl)

    def wave(carry, xs):
        _WAVE_TRACES[0] += 1
        ps, sent, stats = carry
        if cfg.fleet_routing:
            k, mk, feas, rng, L_view = xs
        else:
            k, mk, feas, rng = xs
            # own sends this tick on top of the stale EWMA view
            L_view = state.L_hat + sent
        ctx = RouteContext(
            keys=k,
            mask=mk,
            feas=feas,
            L_view=L_view,
            p50_view=state.p50_hat,
            knobs=knobs,
            now_ms=now_ms,
            rng=rng,
            m=cfg.m,
            fixed_d=cfg.fixed_d,
            route_impl=impl,
        )
        ps, assign, st = policy.route(ps, ctx)
        counts = _wave_counts(cfg.m, mk, assign)
        return (ps, sent + counts, stats + st), None

    xs = (keysg, maskg, feasg, rngs)
    if cfg.fleet_routing:
        # each proxy routes from its OWN staggered telemetry view, with
        # no within-tick sharing across proxies
        xs = xs + (fleet_lib.wave_views(state.L_hat_p, t),)
    init = (
        state.policy,
        jnp.zeros((cfg.m,), jnp.float32),
        RouteStats.zeros(),
    )
    (ps, arrivals, stats), _ = jax.lax.scan(wave, init, xs)
    return ps, arrivals, stats


def _route_waves_unrolled(
    cfg: SimConfig,
    ring: hashring.Ring,
    policy: policy_lib.Policy,
    state: SimState,
    knobs: Knobs,
    t,
    now_ms,
    r_route,
    keysg,
    maskg,
    fc=None,
    fx=None,
):
    """Reference engine: the pre-scan Python loop over waves, O(G) trace
    size, per-wave feasible-set gathers and fold_ins.  Kept for the
    bit-for-bit parity contract and as the E10 "before" baseline.
    Under a membership-changing fault schedule the in-tick gathers go
    member-aware (this tick's detected mask), matching the scan
    engine's per-epoch hoisted gathers key for key."""
    G = keysg.shape[0]
    ps = state.policy
    arrivals = jnp.zeros((cfg.m,), jnp.float32)
    stats = RouteStats.zeros()
    impl = kernels_common.resolve_route_impl(cfg.route_impl)
    member_aware = fc is not None and fc.has_remap
    for g in range(G):
        if cfg.fleet_routing:
            L_view = state.L_hat_p[(g + t) % G]
        else:
            L_view = state.L_hat + arrivals
        if member_aware:
            feas_g = hashring.feasible_set(
                ring, keysg[g], cfg.d_max,
                scan_width=fc.scan_width, member=fx.detected,
            )
        else:
            feas_g = hashring.feasible_set(ring, keysg[g], cfg.d_max)
        ctx = RouteContext(
            keys=keysg[g],
            mask=maskg[g],
            feas=feas_g,
            L_view=L_view,
            p50_view=state.p50_hat,
            knobs=knobs,
            now_ms=now_ms,
            rng=jax.random.fold_in(r_route, g),
            m=cfg.m,
            fixed_d=cfg.fixed_d,
            route_impl=impl,
        )
        ps, assign, st = policy.route(ps, ctx)
        arrivals = arrivals + _wave_counts(cfg.m, maskg[g], assign)
        stats = stats + st
    return ps, arrivals, stats


def _tick(
    cfg: SimConfig,
    ring: hashring.Ring,
    policy: policy_lib.Policy,
    mws: Tuple[mw_lib.Middleware, ...],
    controller: ctrl_lib.Controller,
    fc,
    state: SimState,
    inputs,
) -> Tuple[SimState, TickOut]:
    # ``t`` rides the scan's xs (an unbatched arange) rather than the
    # carried state: under the sweep's vmap a carried counter would be
    # batched, degrading every ``lax.cond`` below to a both-branches
    # ``select`` — with t unbatched the fast/slow cadence work really
    # runs only on its cadence, even inside vmapped sweeps.  The scan
    # engine additionally receives the tick's pre-gathered feasible sets
    # (computed for the whole horizon before the scan — keys don't
    # depend on middleware, so the gather hoists); the unrolled
    # reference keeps its in-tick per-wave gathers, as pre-PR.  With a
    # compiled fault program (``fc``, a trace-time constant), this
    # tick's fault rows (faults.FaultXs) arrive as the last xs entry.
    # Each phase runs under a named scope (``obs_trace.PROGRAM_PHASES``):
    # metadata on the compiled instructions only, results unchanged.
    if fc is not None:
        inputs, fx = inputs[:-1], inputs[-1]
    else:
        fx = None
    if cfg.unroll_waves:
        t, keys, mask, is_write = inputs
        feasg = None
    else:
        t, feasg, keys, mask, is_write = inputs
    now_ms = t.astype(jnp.float32) * cfg.dt_ms
    rng, r_mw, r_route = jax.random.split(state.rng, 3)
    state = state._replace(rng=rng)

    # accumulate the offered batch's write mix (pre-middleware) into the
    # T_slow window counters — Signals.write_mix is the WINDOWED
    # fraction, never a single-tick sample (it would make slow-loop
    # decisions flap on per-tick noise); the slow branch resets the
    # window after the controller consumed it.  Controllers that ignore
    # the signal cost nothing (XLA DCE).
    with jax.named_scope("tick/control"):
        state = state._replace(
            win_writes=state.win_writes
            + jnp.sum((is_write & mask).astype(jnp.float32)),
            win_events=state.win_events
            + jnp.sum(mask.astype(jnp.float32)),
        )

    # --- fault context: remap invalidation BEFORE any stage serves -------
    # (sub-scopes: ``inval``, the owner-table diff inside tick_info;
    # ``remap``, the stages' on_fault, which also counts the live
    # entries it drops)
    finfo = None
    remap_dropped = None
    if fx is not None:
        remap_dropped = jnp.zeros((), jnp.float32)
        with jax.named_scope("tick/faults"):
            finfo = faults_lib.tick_info(fc, fx)
            if finfo.inval is not None:
                with jax.named_scope("remap"):
                    mw_states = []
                    for mw, ms in zip(mws, state.mw):
                        ms, n = mw.on_fault(ms, finfo, cfg, now_ms)
                        mw_states.append(ms)
                        if n is not None:
                            remap_dropped = remap_dropped + n
                    state = state._replace(mw=tuple(mw_states))

    # --- middleware pipeline: stages may absorb requests at the proxy ----
    absorbed = jnp.zeros((), jnp.float32)
    mw_states = list(state.mw)
    with jax.named_scope("tick/middleware"):
        for i, mw in enumerate(mws):
            with jax.named_scope(mw.name):
                batch = mw_lib.BatchView(
                    keys=keys,
                    mask=mask,
                    is_write=is_write,
                    now_ms=now_ms,
                    rng=jax.random.fold_in(r_mw, i),
                    faults=finfo,
                )
                mw_states[i], mask, took = mw.on_batch(
                    mw_states[i], batch, cfg
                )
                absorbed = absorbed + took
    state = state._replace(mw=tuple(mw_states))

    # --- route in waves (scan engine; unrolled reference on request) -----
    with jax.named_scope("tick/route"):
        keysg = _wave_split(cfg, keys)
        maskg = _wave_split(cfg, mask)
        knobs = controller.view(state.ctrl)
        if cfg.unroll_waves:
            ps, arrivals, stats = _route_waves_unrolled(
                cfg,
                ring,
                policy,
                state,
                knobs,
                t,
                now_ms,
                r_route,
                keysg,
                maskg,
                fc,
                fx,
            )
        else:
            ps, arrivals, stats = _route_waves_scan(
                cfg,
                ring,
                policy,
                state,
                knobs,
                t,
                now_ms,
                r_route,
                keysg,
                maskg,
                feasg,
            )
    state = state._replace(policy=ps)

    # --- queue dynamics: constant-rate servers, work-conserving ----------
    with jax.named_scope("tick/queues"):
        L = state.L + arrivals
        if fc is not None and (fc.has_brownout or fc.has_downtime):
            # ground-truth faults bite immediately: browned-out servers
            # drain slower, dead servers not at all (their queue freezes
            # until rejoin)
            rate = jnp.full((cfg.m,), cfg.serve_per_tick, jnp.float32)
            if fc.has_brownout:
                rate = rate * fx.scale
            if fc.has_downtime:
                rate = rate * fx.member.astype(jnp.float32)
            served = jnp.minimum(L, rate)
        else:
            served = jnp.minimum(L, cfg.serve_per_tick)
        L = L - served
        # wait of a new arrival
        lat_pred = (state.L + arrivals) * cfg.service_ms

    state = state._replace(L=L)
    t1 = t + 1  # post-tick clock, the cadence the control loops count on
    # --- telemetry ingest + fast control (every T_fast) ------------------
    with jax.named_scope("tick/control"):
        is_fast = (t1 % cfg.t_fast_ticks) == 0
        sketch = telemetry.sketch_add(state.sketch, lat_pred)

        if cfg.fleet_routing:
            # per-proxy views: each proxy polls on its own staggered phase, so
            # the P views carry genuinely different staleness at any instant
            state = state._replace(
                L_hat_p=telemetry.ewma_staggered(
                    state.L_hat_p,
                    state.L,
                    t1,
                    cfg.t_fast_ticks,
                    ctrl_lib.ALPHA_FAST,
                )
            )

        def _signals(s: SimState, B, p99, jitter) -> Signals:
            # availability / membership telemetry: constants (full) on the
            # zero-fault path, this tick's detected view under a schedule
            if fx is None:
                avail = jnp.ones(())
                member = jnp.ones((cfg.m,))
            else:
                avail = fx.avail
                member = fx.detected.astype(jnp.float32)
            return Signals(
                B=B,
                p99=p99,
                L_hat=s.L_hat,
                views_p=s.L_hat_p,
                write_mix=s.win_writes / jnp.maximum(s.win_events, 1.0),
                jitter=jitter,
                rtt_ms=cfg.rtt_ms,
                avail=avail,
                member=member,
            )

        def ingest(s: SimState) -> SimState:
            # quantile extraction (a per-server sort) lives INSIDE the fast
            # branch: with t unbatched the sort really runs once per fast
            # interval, not every tick
            p50_o, p99_o = telemetry.sketch_quantiles(s.sketch)
            if cfg.fleet_routing:
                # one control loop fed by the fleet's consensus view
                L_hat = ctrl_lib.consensus_view(s.L_hat_p, cfg.consensus)
            else:
                L_hat = telemetry.ewma(s.L_hat, s.L, ctrl_lib.ALPHA_FAST)
            p50 = telemetry.ewma(s.p50_hat, p50_o, ctrl_lib.ALPHA_FAST)
            p99 = telemetry.ewma(s.p99_hat, p99_o, ctrl_lib.ALPHA_FAST)
            if fc is not None and fc.has_remap:
                # survivors-only imbalance: a dead server's frozen queue
                # must not pin B(t) for the whole outage
                B = telemetry.imbalance_masked(L_hat, fx.detected)
            else:
                B = telemetry.imbalance(L_hat)
            jit = jax.random.uniform(
                jax.random.fold_in(s.rng, 3), (), minval=-1.0, maxval=1.0
            )
            s = s._replace(L_hat=L_hat, p50_hat=p50, p99_hat=p99)
            ctrl, _ = controller.fast(
                s.ctrl, _signals(s, B, jnp.max(p99), jit)
            )
            return s._replace(ctrl=ctrl)

        state = state._replace(sketch=sketch)
        state = jax.lax.cond(is_fast, ingest, lambda s: s, state)

        is_slow = (t1 % cfg.t_slow_ticks) == 0

        def slow(s: SimState) -> SimState:
            if fc is not None and fc.has_remap:
                B_slow = telemetry.imbalance_masked(s.L_hat, fx.detected)
            else:
                B_slow = telemetry.imbalance(s.L_hat)
            ctrl, k = controller.slow(
                s.ctrl,
                _signals(
                    s,
                    B_slow,
                    jnp.max(s.p99_hat),
                    jnp.zeros((), jnp.float32),
                ),
            )
            return s._replace(
                ctrl=ctrl,
                mw=tuple(
                    mw.on_slow(ms, cfg, k) for mw, ms in zip(mws, s.mw)
                ),
                # window consumed: write-mix restarts for the next T_slow
                win_writes=jnp.zeros((), jnp.float32),
                win_events=jnp.zeros((), jnp.float32),
            )

        state = jax.lax.cond(is_slow, slow, lambda s: s, state)

    out = TickOut(
        L=L,
        arrivals=arrivals,
        lat_pred=lat_pred,
        d=state.ctrl.knobs.d,
        delta_l=state.ctrl.knobs.delta_l,
        f_max=state.ctrl.knobs.f_max,
        pressure=state.ctrl.pressure,
        steered=stats.steered,
        eligible=stats.eligible,
        cache_hits=absorbed,
        dV=stats.dV,
        remap_dropped=remap_dropped,
    )
    return state, out


def init_state(
    cfg: SimConfig, b_tgt: float = 0.15, p99_tgt: float = 500.0
) -> SimState:
    policy = policy_lib.get(cfg.policy)  # raises with available() names
    ring = hashring.make_ring(cfg.m, cfg.V)
    return SimState(
        L=jnp.zeros((cfg.m,), jnp.float32),
        L_hat=jnp.zeros((cfg.m,), jnp.float32),
        L_hat_p=jnp.zeros((cfg.P, cfg.m), jnp.float32),
        p50_hat=jnp.zeros((cfg.m,), jnp.float32),
        p99_hat=jnp.zeros((cfg.m,), jnp.float32),
        sketch=telemetry.make_sketch(cfg.m),
        policy=policy.init(cfg, ring),
        ctrl=_controller(cfg).init(cfg, (b_tgt, p99_tgt)),
        mw=tuple(mw.init(cfg) for mw in _middlewares(cfg)),
        win_writes=jnp.zeros((), jnp.float32),
        win_events=jnp.zeros((), jnp.float32),
        rng=jax.random.PRNGKey(cfg.seed),
    )


def _scan_inputs(
    cfg: SimConfig, ring: hashring.Ring, keys, mask, is_write, fc=None
):
    """Per-tick scan inputs for one (T, R) workload grid.

    The tick clock is an unbatched arange (see ``_tick``).  For the scan
    engine, the feasible sets for the ENTIRE horizon are gathered here in
    one batched call — (T, G, R/G, d_max) riding the scan's xs — so key
    hashing and the first-occurrence scan leave the per-tick path
    completely.  The unrolled reference keeps its in-tick gathers.

    With a compiled fault schedule (``fc``), storm traffic is overlaid
    on the workload grid first (so the hoisted gathers see the storm
    keys), membership epochs make the hoisted gathers member-aware, and
    the per-tick fault rows (``faults.FaultXs``) join the xs tuple.
    """
    if fc is not None and fc.has_storm:
        keys, mask, is_write = faults_lib.apply_traffic(
            fc, keys, mask, is_write
        )
    ticks = jnp.arange(keys.shape[0], dtype=jnp.int32)
    if cfg.unroll_waves:
        base = (ticks, keys, mask, is_write)
    else:
        with jax.named_scope("sweep/feasible"):
            keysg = _wave_split(cfg, keys)
            if fc is not None:
                feasg = faults_lib.feasible_by_epoch(
                    ring, keysg, cfg.d_max, fc
                )
            else:
                feasg = hashring.feasible_set(ring, keysg, cfg.d_max)
        base = (ticks, feasg, keys, mask, is_write)
    if fc is not None:
        base = base + (faults_lib.make_xs(fc),)
    return base


# Trace counter for _run_scan: increments once per (re)trace, so the
# host-side obs spans can tag whether a call paid compilation — a
# Python-list mutation at trace time, invisible to the compiled math
# (the golden-parity contract is untouched).
_RUN_TRACES = [0]


@functools.partial(jax.jit, static_argnums=(0,))
def _run_scan(cfg: SimConfig, state: SimState, keys, mask, is_write):
    _RUN_TRACES[0] += 1
    ring = hashring.make_ring(cfg.m, cfg.V)
    fc = faults_lib.compile_faults(cfg, int(keys.shape[0]))
    step = functools.partial(
        _tick,
        cfg,
        ring,
        policy_lib.get(cfg.policy),
        _middlewares(cfg),
        _controller(cfg),
        fc,
    )
    xs = _scan_inputs(cfg, ring, keys, mask, is_write, fc)
    return jax.lax.scan(step, state, xs)


# Trace counter for _run_scan_sweep: increments only when the sweep scan is
# (re)compiled, letting tests assert "one compile per policy, any #seeds".
_SWEEP_TRACES = [0]


def _sweep_vmapped(
    cfg: SimConfig,
    states: SimState,
    keys,
    mask,
    is_write,
    metrics: str = "full",
):
    """The sweep body shared by the single-device jit (below) and the
    sharded runner (``repro.core.sweep``): nested vmap over (W, S) with
    identical per-cell math — what makes the sharded-vs-vmap parity
    contract bit-for-bit rather than merely approximate."""
    ring = hashring.make_ring(cfg.m, cfg.V)
    fc = faults_lib.compile_faults(cfg, int(keys.shape[1]))
    step = functools.partial(
        _tick,
        cfg,
        ring,
        policy_lib.get(cfg.policy),
        _middlewares(cfg),
        _controller(cfg),
        fc,
    )

    def run(st, k, mk, w):
        # unbatched tick clock + per-workload hoisted feasible sets: both
        # stay unbatched under the seed vmap (computed once per workload)
        grids = _scan_inputs(cfg, ring, k, mk, w, fc)
        if metrics == "summary":

            def tick(carry, xs):
                s, acc = carry
                s, out = step(s, xs)
                ys = KnobTrace(
                    d=out.d,
                    delta_l=out.delta_l,
                    f_max=out.f_max,
                    pressure=out.pressure,
                    q_mean=jnp.mean(out.L),
                )
                with jax.named_scope("tick/summary"):
                    acc = _summary_update(acc, out)
                return (s, acc), ys

            (final, acc), trace = jax.lax.scan(
                tick,
                (st, _summary_init(cfg.m, fc is not None)),
                grids,
            )
            return final, (acc, trace)
        return jax.lax.scan(step, st, grids)

    return jax.vmap(
        lambda k, mk, w: jax.vmap(lambda st: run(st, k, mk, w))(states)
    )(keys, mask, is_write)


@functools.partial(jax.jit, static_argnums=(0, 5))
def _run_scan_sweep(
    cfg: SimConfig,
    states: SimState,
    keys,
    mask,
    is_write,
    metrics: str = "full",
):
    """Batched scan: ``states`` carries a leading seed axis (S, ...) and
    the workload grids a leading workload axis (W, T, R).

    The seed axis rides an INNER vmap with the grids held constant
    (closed over, i.e. ``in_axes=None`` semantics), so per-tick work
    that does not depend on the seed — key hashing, the batched
    feasible-set gather — is computed once per workload, not once per
    (workload, seed) combo, and nothing is ``jnp.repeat``-duplicated.
    Returns ``(final, outs)`` pytrees with leading (W, S) axes; ``outs``
    is the stacked TickOut timeline under ``metrics="full"`` and the
    O(m) :class:`SummaryAcc` under ``"summary"``.
    """
    _SWEEP_TRACES[0] += 1
    return _sweep_vmapped(cfg, states, keys, mask, is_write, metrics)


def warmup(
    cfg: SimConfig, T: int = 1200, seed: int = 99
) -> Tuple[float, float]:
    """§III-B: run at ≤30% utilization with no middleware, derive
    targets."""
    from repro.core.workloads import make_workload

    wl = make_workload(
        "light",
        T=T,
        m=cfg.m,
        seed=seed,
        dt_ms=cfg.dt_ms,
        service_ms=cfg.service_ms,
        N=cfg.N,
    )
    warm_cfg = dataclasses.replace(
        cfg, policy="hash", cache_enabled=False, middleware=(), faults=None
    )
    st = init_state(warm_cfg)
    with obs_trace.span("sim/warmup", cat="warmup", T=T, m=cfg.m):
        _, outs = _run_scan(warm_cfg, st, wl.keys, wl.mask, wl.is_write)
        jax.block_until_ready(outs.L)
    L = np.asarray(outs.L)
    # EWMA'd imbalance series, same smoothing as the controller —
    # vectorized closed-form filter (was an O(T) host-side Python loop)
    L_hat = telemetry.ewma_series(L, ctrl_lib.ALPHA_FAST)
    B = L_hat.std(axis=1) / (L_hat.mean(axis=1) + ctrl_lib.EPS)
    w = np.asarray(outs.arrivals)
    if w.sum() > 0:
        (p99_warm,) = telemetry.weighted_quantiles(
            np.asarray(outs.lat_pred), w, (99,)
        )
    else:
        p99_warm = cfg.service_ms
    b_tgt = float(np.median(B) + 0.05)
    p99_tgt = float(max(1.25 * p99_warm, cfg.rtt_ms + 2.0))
    return b_tgt, p99_tgt


def _final_cache(cfg: SimConfig, final: SimState):
    """Final cache pytree: the shared-table CacheState for "cache", the
    FleetState (converged table + per-proxy counters) for "fleet_cache"."""
    chain = cfg.middleware_chain
    for name in ("cache", "fleet_cache"):
        if name in chain:
            return jax.device_get(final.mw[chain.index(name)])
    return None


def _to_result(cfg: SimConfig, outs: TickOut, final_cache) -> SimResult:
    return SimResult(
        queue_timeline=np.asarray(outs.L),
        arrivals=np.asarray(outs.arrivals),
        lat_pred=np.asarray(outs.lat_pred),
        d_timeline=np.asarray(outs.d),
        delta_l_timeline=np.asarray(outs.delta_l),
        pressure=np.asarray(outs.pressure),
        steered=np.asarray(outs.steered),
        eligible=np.asarray(outs.eligible),
        cache_hits=np.asarray(outs.cache_hits),
        final_cache=final_cache,
        config=cfg,
        f_max_timeline=np.asarray(outs.f_max),
    )


def _targets(cfg: SimConfig, do_warmup: bool) -> Tuple[float, float]:
    if do_warmup and policy_lib.get_class(cfg.policy).adaptive:
        return warmup(cfg)
    return 0.15, 5.0 * cfg.service_ms


def simulate(
    cfg: SimConfig, wl: Workload, do_warmup: bool = True
) -> SimResult:
    b_tgt, p99_tgt = _targets(cfg, do_warmup)
    state = init_state(cfg, b_tgt, p99_tgt)
    traces0 = _RUN_TRACES[0]
    with obs_trace.span(
        "sim/run",
        cat="execute",
        policy=cfg.policy,
        controller=cfg.controller,
        T=int(wl.keys.shape[0]),
    ) as sp:
        final, outs = _run_scan(cfg, state, wl.keys, wl.mask, wl.is_write)
        jax.block_until_ready(outs.L)
        sp["compiled"] = _RUN_TRACES[0] > traces0
    with obs_trace.span("sim/host_result", cat="host"):
        return _to_result(cfg, outs, _final_cache(cfg, final))


# per-seed rows for one (policy, workload) combo
SweepRows = Tuple[Union[SimResult, SummaryResult], ...]

# Module-level once-per-process guard for the simulate_sweep
# DeprecationWarning: sweeps call the shim in loops, and one nag per
# process is signal while one per call is noise.  Tests reset it to
# assert the exactly-once contract.
_SWEEP_DEPRECATION_WARNED = [False]


def simulate_sweep(
    cfg: SimConfig,
    wl: Union[Workload, Sequence[Workload]],
    policies: Optional[Tuple[str, ...]] = None,
    seeds: Tuple[int, ...] = (0,),
    do_warmup: bool = True,
    metrics: str = "full",
    targets: Optional[Tuple[float, float]] = None,
) -> Union[Dict[str, SweepRows], Dict[str, Dict[str, SweepRows]]]:
    """Batched simulation: fan-out over ``policies × workloads × seeds``.

    ``wl`` is a single :class:`Workload` or a sequence of them (same grid
    shape, e.g. built under one set of ``make_workload`` params).  For
    each policy the scan is traced and compiled exactly once: workload
    grids ride an outer ``vmap`` axis and seeds an inner one that shares
    the grids (so seed-independent work — key hashing, the feasible-set
    gather — runs once per workload; per-seed/per-workload ``simulate``
    calls would each retrace, since ``cfg.seed`` is static).

    ``metrics="full"`` (default) returns :class:`SimResult` rows with
    complete (T, m) timelines.  ``metrics="summary"`` carries O(m)
    streaming accumulators through the scan instead and returns
    :class:`SummaryResult` rows — same paper-metric API, sweep memory
    O(B·m) instead of O(B·T·m), which is what lets E8/E9-scale matrices
    run many seeds per cell (DESIGN.md §9).

    ``targets`` pins the §III-B control targets ``(b_tgt, p99_tgt)``
    explicitly, skipping the per-policy warmup pass entirely — the
    warmup is policy- and controller-independent (it runs the ``hash``
    policy bare), so callers sweeping a grid of configs over one
    environment (e.g. E4's controller matrix) can run it once and share
    the result instead of recompiling it per cell.

    Returns ``{policy: (row per seed, ...)}`` for a single workload (the
    legacy shape) and ``{policy: {workload_name: (row per seed, ...)}}``
    for a sequence; per-combo full-metrics results match individual
    ``simulate`` runs.

    .. deprecated::
        ``simulate_sweep`` is a thin shim over the declarative API —
        build a :class:`repro.core.sweep.SweepSpec` and call
        :func:`repro.core.sweep.run_sweep` instead, which adds the
        controller axis, multi-device sharding, and a coordinate-
        addressable :class:`repro.core.sweep.SweepResult`.
    """
    if not _SWEEP_DEPRECATION_WARNED[0]:
        _SWEEP_DEPRECATION_WARNED[0] = True
        warnings.warn(
            "simulate_sweep is deprecated; build a repro.core.sweep."
            "SweepSpec and call run_sweep (DESIGN.md §12)",
            DeprecationWarning,
            stacklevel=2,
        )
    from repro.core import sweep as sweep_lib

    single = isinstance(wl, Workload)
    spec = sweep_lib.SweepSpec(
        config=cfg,
        workloads=wl,
        policies=tuple(policies) if policies is not None else None,
        seeds=tuple(seeds),
        metrics=metrics,
        do_warmup=do_warmup,
        targets=targets,
    )
    return sweep_lib.run_sweep(spec).to_legacy(single=single)
