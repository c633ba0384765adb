"""One cell of the benchmark, built from its deployment and traffic
files: the grids from the seed, the program's ``SweepSpec`` and the
reference's view of the same deployment.

The program is imported here and in the runner only; the traffic
generators and the reference import nothing of it.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import numpy as np

import traffic_gen
from midasbench import reference

# deployment settings the program and the reference both take
SIM_KEYS = (
    "m", "P", "N", "V", "dt_ms", "service_ms", "d_max", "rtt_ms",
    "n_groups", "lease_ms", "gossip_ms", "fleet_routing",
)
# settings the reference implements one value of
FIXED = {"cache_mode": "lease", "consensus": "mean"}


@dataclasses.dataclass
class Cell:
    name: str
    config: Dict
    traffic: Dict
    dep: reference.Deployment
    grids: Dict[str, traffic_gen.Grid]
    sim_seeds: Tuple[int, ...]
    targets: Tuple[float, float] = None  # pinned, or None: warmup
    spec: object = None  # the program's SweepSpec

    @property
    def T(self) -> int:
        return int(self.traffic["T"])

    @property
    def grid_cells(self) -> int:
        return len(self.grids) * len(self.sim_seeds)

    @property
    def coords(self):
        return [(w, s) for w in self.grids for s in self.sim_seeds]


def seeds_from(seed: int, n: int) -> Tuple[int, Tuple[int, ...]]:
    """The grid seed and ``n`` distinct simulation seeds named by one
    run seed (any whole number)."""
    rng = np.random.default_rng(int(seed) % 2**64)
    grid_seed = int(rng.integers(0, 2**30))
    sims = rng.choice(2**31 - 1, size=n, replace=False)
    return grid_seed, tuple(int(s) for s in sims)


def build(bench, workload: str, seed: int) -> Cell:
    """The cell's grids, seeds and reference deployment (no program)."""
    w = bench.workload(workload)
    cfg = bench.config(w["config"])
    tr = bench.traffic(w["traffic"])
    sim = cfg["sim"]
    for k, v in FIXED.items():
        if sim.get(k, v) != v:
            raise ValueError(f"{w['config']}: {k}={sim[k]!r} is not {v!r}")
    if tr.get("controller", "hysteresis") != "hysteresis":
        raise ValueError(f"{w['traffic']}: only the hysteresis controller")
    dep = reference.Deployment(
        **{k: sim[k] for k in SIM_KEYS},
        fixed_d=int(tr.get("fixed_d", 2)),
        policy=tr["policy"],
        middleware=tuple(tr["middleware"]),
    )
    grid_seed, sim_seeds = seeds_from(seed, int(tr["seeds_per_sweep"]))
    grids = {
        s: traffic_gen.make(
            s,
            T=int(tr["T"]),
            m=dep.m,
            seed=grid_seed,
            N=dep.N,
            R=int(tr["R"]),
            dt_ms=dep.dt_ms,
            service_ms=dep.service_ms,
        )
        for s in tr["scenarios"]
    }
    pinned = tr.get("targets")
    return Cell(
        name=workload,
        config=cfg,
        traffic=tr,
        dep=dep,
        grids=grids,
        sim_seeds=sim_seeds,
        targets=None if pinned is None else tuple(map(float, pinned)),
    )


def attach_program(cell: Cell) -> Cell:
    """The program's ``SweepSpec`` for the cell, fed the cell's grids."""
    from repro.core import SimConfig, SweepSpec
    from repro.core.workloads import Workload

    sim, tr = cell.config["sim"], cell.traffic
    cfg = SimConfig(
        **{k: sim[k] for k in SIM_KEYS},
        cache_mode=FIXED["cache_mode"],
        consensus=FIXED["consensus"],
        policy=tr["policy"],
        middleware=tuple(tr["middleware"]),
        controller=tr.get("controller", "hysteresis"),
        fixed_d=int(tr.get("fixed_d", 2)),
    )
    wls = tuple(
        Workload(g.keys, g.mask, g.is_write, name, cell.dep.N)
        for name, g in cell.grids.items()
    )
    cell.spec = SweepSpec(
        config=cfg,
        workloads=wls,
        policies=(tr["policy"],),
        seeds=cell.sim_seeds,
        metrics="summary",
        devices=int(tr.get("devices", 1)),
        do_warmup=bool(tr.get("warmup", False)),
        targets=cell.targets,
    )
    return cell


def rows_of(cell: Cell, result) -> Dict[Tuple[str, int], Dict]:
    """The program's summary rows as reference-shaped field dicts."""
    out = {}
    for w, s in cell.coords:
        r = result.row(workload=w, seed=s)
        vals = (
            r.queue_sum, r.queue_max_v, r.cv_sum, r.cv_count, r.queue_hist,
            r.lat_hist, r.arrivals_total, r.steered_total, r.eligible_total,
            r.cache_hits_total, r.d_timeline, r.delta_l_timeline,
            r.f_max_timeline, r.pressure, r.q_mean_timeline,
        )
        out[(w, s)] = {
            f: np.asarray(v, np.float64)
            for f, v in zip(reference.FIELDS, vals)
        }
    return out


def reference_targets(cell: Cell) -> Tuple[float, float]:
    """The control targets: pinned by the traffic file, or the warmup's
    (adaptive policies only, as the program runs it)."""
    if cell.targets is not None:
        return cell.targets
    if cell.traffic.get("warmup") and cell.dep.policy == "midas":
        light = traffic_gen.make(
            "light",
            T=reference.WARMUP_T,
            m=cell.dep.m,
            seed=reference.WARMUP_SEED,
            N=cell.dep.N,
            dt_ms=cell.dep.dt_ms,
            service_ms=cell.dep.service_ms,
        )
        return reference.warmup_targets(cell.dep, light)
    return 0.15, 5.0 * cell.dep.service_ms
