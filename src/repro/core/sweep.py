"""Declarative sweep engine: ``SweepSpec`` -> :func:`run_sweep` ->
``SweepResult``, with optional multi-device sharding.

``simulate_sweep`` accreted positional grids and a legacy
single-workload return shape; this module is its redesign.  A sweep is
now DATA — one frozen :class:`SweepSpec` naming the (policy ×
controller × workload × seed) grid, the metrics mode, the fault
schedule, and the device mesh — validated eagerly at construction with
the same list-alternatives errors as ``SimConfig`` (shared
``repro.core.registry`` helpers).  :func:`run_sweep` executes it and
returns a :class:`SweepResult` addressable by grid coordinates instead
of nested dicts.  The old ``simulate_sweep`` survives as a deprecation
shim on top of this module.

Sharding (DESIGN.md §12).  ``SweepSpec(devices=n)`` partitions the SEED
axis of each (policy, controller) batch over an n-device mesh with
``jax.shard_map``: workload grids are replicated (``P()`` — they are
seed-independent, and the per-workload feasible-set gather stays one
batched call *per device*, never O(cells)), while every leaf of the
stacked ``SimState`` is split on its leading seed axis.  Each device
runs the IDENTICAL nested-vmap body as the single-device path
(``sim._sweep_vmapped``), so sharded results are bit-for-bit the
single-device vmap results — tested under
``--xla_force_host_platform_device_count=8`` for both metrics modes.
Seeds that don't divide ``devices`` are padded with repeats of the last
state and the padded rows dropped on host.

Memory stays flat in the namespace size R (paper scale: R ≈ 10⁶ keys,
P in the hundreds of proxies): nothing materializes O(R·P) — the ring
is O(m·V), the gather output O(T·R_slots·d_max), and per-key state
(pins, cache tables) O(R) per cell at 4–8 bytes/key.  E11
(``benchmarks/shard_sweep.py``) measures both claims.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterator, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import controllers as ctrl_lib
from repro.core import policies as policy_lib
from repro.core import registry as registry_lib
from repro.core import sim
from repro.core.workloads import Workload
from repro.obs import trace as obs_trace

# one realized row of the grid: full timelines or the streaming summary
Row = Union[sim.SimResult, sim.SummaryResult]
# grid coordinates: (policy, controller, workload name, seed)
Coord = Tuple[str, str, str, int]


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """One declarative sweep: the full grid, validated at construction.

    ``workloads`` accepts a single :class:`Workload` or a sequence
    (coerced to a tuple; grids must share one shape and names must be
    unique).  ``policies`` / ``controllers`` default to the config's
    single policy / controller.  ``faults`` overrides ``config.faults``
    when not ``None`` (pass ``()`` to force the zero-fault engine).
    ``devices=1`` is the plain nested-vmap engine; ``devices=n`` shards
    the seed axis over n devices (see module docstring).  ``targets``
    pins the §III-B control targets, skipping the per-policy warmup.
    """

    config: sim.SimConfig
    workloads: Tuple[Workload, ...]
    policies: Optional[Tuple[str, ...]] = None
    controllers: Optional[Tuple[str, ...]] = None
    seeds: Tuple[int, ...] = (0,)
    metrics: str = "full"
    devices: int = 1
    faults: Optional[Tuple] = None
    do_warmup: bool = True
    targets: Optional[Tuple[float, float]] = None

    def __post_init__(self):
        # -- workload grid ------------------------------------------------
        wls = (
            (self.workloads,)
            if isinstance(self.workloads, Workload)
            else tuple(self.workloads)
        )
        object.__setattr__(self, "workloads", wls)
        if not wls:
            raise ValueError("SweepSpec needs at least one workload")
        shapes = {w.keys.shape for w in wls}
        if len(shapes) > 1:
            raise ValueError(
                f"SweepSpec workloads must share one grid "
                f"shape; got {sorted(shapes)}"
            )
        names = [w.name for w in wls]
        if len(set(names)) != len(names):
            raise ValueError(
                f"SweepSpec workload names must be unique; got {names}"
            )
        # -- policy / controller axes (registry-validated) ----------------
        pols = (
            (self.config.policy,)
            if self.policies is None
            else tuple(self.policies)
        )
        for p in pols:
            policy_lib.get_class(p)  # raises with alternatives
        object.__setattr__(self, "policies", pols)
        ctrls = (
            (self.config.controller,)
            if self.controllers is None
            else tuple(self.controllers)
        )
        for c in ctrls:
            ctrl_lib.get_class(c)
        object.__setattr__(self, "controllers", ctrls)
        # -- seeds / metrics / mesh ---------------------------------------
        seeds = tuple(int(s) for s in self.seeds)
        object.__setattr__(self, "seeds", seeds)
        if not seeds:
            raise ValueError("SweepSpec needs at least one seed")
        registry_lib.validate_choice(
            self.metrics, "metrics mode", sim.METRICS_MODES
        )
        d = self.devices
        if not isinstance(d, int) or isinstance(d, bool) or d <= 0:
            raise ValueError(
                f"SweepSpec.devices must be a positive int, got {d!r}"
            )
        # -- fault override: folded into the config (and validated by
        #    SimConfig.__post_init__, which canonicalizes the events)
        if self.faults is not None:
            object.__setattr__(
                self,
                "config",
                dataclasses.replace(self.config, faults=self.faults),
            )
        if self.targets is not None:
            b_tgt, p99_tgt = self.targets
            object.__setattr__(self, "targets", (float(b_tgt), float(p99_tgt)))

    # -- grid views -------------------------------------------------------
    @property
    def workload_names(self) -> Tuple[str, ...]:
        return tuple(w.name for w in self.workloads)

    @property
    def n_cells(self) -> int:
        return (
            len(self.policies)
            * len(self.controllers)
            * len(self.workloads)
            * len(self.seeds)
        )

    def coords(self) -> Iterator[Coord]:
        """Grid coordinates in execution order."""
        for p in self.policies:
            for c in self.controllers:
                for w in self.workload_names:
                    for s in self.seeds:
                        yield (p, c, w, s)


@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Realized grid: one :class:`Row` per (policy, controller,
    workload, seed) coordinate of the spec."""

    spec: SweepSpec
    cells: Dict[Coord, Row]

    def _pick(self, kind: str, value, options) -> str:
        if value is not None:
            return registry_lib.validate_choice(value, kind, options)
        if len(options) == 1:
            return options[0]
        raise ValueError(
            f"ambiguous {kind}: the sweep has {len(options)} "
            f"({', '.join(str(o) for o in options)}); name one"
        )

    def rows(
        self,
        policy: Optional[str] = None,
        controller: Optional[str] = None,
        workload: Optional[str] = None,
    ) -> Tuple[Row, ...]:
        """Per-seed rows of one grid cell.  Axes with a single value in
        the spec may be omitted; multi-valued axes must be named."""
        p = self._pick("policy", policy, self.spec.policies)
        c = self._pick("controller", controller, self.spec.controllers)
        w = self._pick("workload", workload, self.spec.workload_names)
        return tuple(self.cells[(p, c, w, s)] for s in self.spec.seeds)

    def row(
        self,
        policy: Optional[str] = None,
        controller: Optional[str] = None,
        workload: Optional[str] = None,
        seed: Optional[int] = None,
    ) -> Row:
        """One realized run (seed defaulted when the spec has one)."""
        p = self._pick("policy", policy, self.spec.policies)
        c = self._pick("controller", controller, self.spec.controllers)
        w = self._pick("workload", workload, self.spec.workload_names)
        s = self._pick("seed", seed, self.spec.seeds)
        return self.cells[(p, c, w, s)]

    def items(self):
        """((policy, controller, workload, seed), row) pairs."""
        return self.cells.items()

    def to_legacy(self, single: bool):
        """The pre-SweepSpec ``simulate_sweep`` return shapes:
        ``{policy: rows}`` for a single workload, ``{policy:
        {workload: rows}}`` otherwise.  Requires a single-controller
        spec (the legacy API had no controller axis)."""
        if len(self.spec.controllers) != 1:
            raise ValueError(
                "legacy sweep shape has no controller axis; the spec "
                f"names {len(self.spec.controllers)} controllers"
            )
        (ctrl,) = self.spec.controllers
        out: Dict[str, dict] = {}
        for p in self.spec.policies:
            per_wl = {
                w: self.rows(policy=p, controller=ctrl, workload=w)
                for w in self.spec.workload_names
            }
            out[p] = per_wl[self.spec.workload_names[0]] if single else per_wl
        return out


# ---------------------------------------------------------------------------
# Sharded runner (devices > 1)
# ---------------------------------------------------------------------------


# Trace counter mirroring sim._SWEEP_TRACES: one (re)compile per
# (config, metrics, devices), regardless of #seeds/#workloads.
_SHARD_TRACES = [0]


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def _run_scan_sweep_sharded(
    cfg: sim.SimConfig,
    states: sim.SimState,
    keys,
    mask,
    is_write,
    metrics: str,
    n_dev: int,
):
    """``sim._run_scan_sweep`` with the seed axis split over ``n_dev``
    devices.  The body each device runs is ``sim._sweep_vmapped`` —
    shared with the single-device jit, which is what makes the parity
    contract bit-for-bit.  Workload grids ride in replicated (they are
    seed-independent); every output leaf is (W, S, ...), so a single
    ``P(None, "dev")`` prefix reassembles the seed axis.
    """
    _SHARD_TRACES[0] += 1
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("dev",))

    def body(sts, k, mk, w):
        return sim._sweep_vmapped(cfg, sts, k, mk, w, metrics)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P("dev"), P(), P(), P()),
        out_specs=P(None, "dev"),
        check_vma=False,
    )
    return fn(states, keys, mask, is_write)


def _check_devices(n_dev: int) -> None:
    have = len(jax.devices())
    if n_dev > have:
        raise ValueError(
            f"SweepSpec.devices={n_dev} but only {have} JAX device(s) "
            f"are visible; on CPU, launch with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_dev} "
            f"set BEFORE jax initializes"
        )


def _pad_seed_axis(states, n_seeds: int, n_dev: int):
    """Pad the leading seed axis to a multiple of n_dev by repeating the
    last state; padded rows compute throwaway cells dropped on host."""
    pad = (-n_seeds) % n_dev
    if pad == 0:
        return states, 0
    states = jax.tree_util.tree_map(
        lambda x: jnp.concatenate(
            [x, jnp.repeat(x[-1:], pad, axis=0)], axis=0
        ),
        states,
    )
    return states, pad


# ---------------------------------------------------------------------------
# Execution
# ---------------------------------------------------------------------------


def run_sweep(spec: SweepSpec) -> SweepResult:
    """Execute a :class:`SweepSpec`.

    One compiled scan per (policy, controller) — seeds and workloads
    ride vmap axes (sharded over the mesh when ``devices > 1``), and the
    §III-B warmup (when enabled and the policy is adaptive) runs once
    per policy, shared across controllers.  One device transfer per
    (policy, controller) batch, sliced on host into :class:`Row` cells.
    """
    cfg = spec.config
    if spec.devices > 1:
        _check_devices(spec.devices)
    wls = spec.workloads
    # (W, T, R) grids — shared across the seed axis, never duplicated
    keys = jnp.stack([w.keys for w in wls])
    mask = jnp.stack([w.mask for w in wls])
    is_write = jnp.stack([w.is_write for w in wls])
    targets_by_policy: Dict[str, Tuple[float, float]] = {}
    cells: Dict[Coord, Row] = {}
    for pname in spec.policies:
        for cname in spec.controllers:
            pcfg = dataclasses.replace(cfg, policy=pname, controller=cname)
            if spec.targets is not None:
                b_tgt, p99_tgt = spec.targets
            else:
                # warmup is policy- and controller-independent (it runs
                # the bare "hash" policy): one pass per policy, shared
                # across the controller axis
                if pname not in targets_by_policy:
                    with obs_trace.span(
                        "sweep/warmup", cat="warmup", policy=pname
                    ):
                        targets_by_policy[pname] = sim._targets(
                            pcfg, spec.do_warmup
                        )
                b_tgt, p99_tgt = targets_by_policy[pname]
            with obs_trace.span(
                "sweep/init_states",
                cat="host",
                policy=pname,
                controller=cname,
                seeds=len(spec.seeds),
            ):
                per_seed = [
                    sim.init_state(
                        dataclasses.replace(pcfg, seed=s), b_tgt, p99_tgt
                    )
                    for s in spec.seeds
                ]
                states = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *per_seed
                )
            traces0 = sim._SWEEP_TRACES[0] + _SHARD_TRACES[0]
            with obs_trace.span(
                "sweep/execute",
                cat="execute",
                policy=pname,
                controller=cname,
                metrics=spec.metrics,
                devices=spec.devices,
                workloads=len(wls),
                seeds=len(spec.seeds),
            ) as sp:
                with obs_trace.span("sweep/dispatch", cat="dispatch"):
                    if spec.devices > 1:
                        states, pad = _pad_seed_axis(
                            states, len(spec.seeds), spec.devices
                        )
                        fn = _run_scan_sweep_sharded
                        args = (
                            pcfg,
                            states,
                            keys,
                            mask,
                            is_write,
                            spec.metrics,
                            spec.devices,
                        )
                    else:
                        pad = 0
                        fn = sim._run_scan_sweep
                        args = (
                            pcfg, states, keys, mask, is_write, spec.metrics
                        )
                    final, outs = fn(*args)
                # one transfer for the whole batch, sliced on host
                with obs_trace.span("sweep/transfer", cat="transfer"):
                    outs = jax.device_get(outs)
                    if spec.metrics == "full":
                        final = jax.device_get(final)
                sp["compiled"] = (
                    sim._SWEEP_TRACES[0] + _SHARD_TRACES[0] > traces0
                )
                if sp["compiled"] and obs_trace.RECORDER.enabled:
                    # the executable the call just built: a cache hit,
                    # no new trace (the trace counters stay put)
                    obs_trace.register_program(fn.lower(*args).compile())
            del pad, args  # padded rows simply never get sliced below
            with obs_trace.span(
                "sweep/host_slice",
                cat="host",
                policy=pname,
                controller=cname,
                cells=len(wls) * len(spec.seeds),
            ):
                for j, w in enumerate(wls):
                    for i, s in enumerate(spec.seeds):
                        scfg = dataclasses.replace(pcfg, seed=s)
                        row = jax.tree_util.tree_map(lambda x: x[j, i], outs)
                        if spec.metrics == "summary":
                            # row is the (SummaryAcc, KnobTrace) pair
                            cells[(pname, cname, w.name, s)] = sim._to_summary(
                                scfg, *row
                            )
                        else:
                            final_b = jax.tree_util.tree_map(
                                lambda x: x[j, i], final
                            )
                            cells[(pname, cname, w.name, s)] = (
                                sim._to_result(
                                    scfg,
                                    row,
                                    sim._final_cache(pcfg, final_b),
                                )
                            )
    return SweepResult(spec=spec, cells=cells)
