"""One cell of the benchmark, built from its deployment and traffic
files: the grids from the seed (the traffic file's generator), the
program's ``SweepSpec`` and the deployment as the configuration's
reference sees it.

The program is imported here and in the runner only; the traffic
generators and the references import nothing of it.
"""

from __future__ import annotations

import dataclasses
from types import ModuleType
from typing import Dict, NamedTuple, Tuple

import numpy as np


@dataclasses.dataclass
class Cell:
    name: str
    config: Dict
    traffic: Dict
    ref: ModuleType  # the configuration's reference module
    gen: ModuleType  # the traffic file's generator module
    dep: object  # ref.deployment(...)
    grids: Dict[str, NamedTuple]
    sim_seeds: Tuple[int, ...]
    spec: object = None  # the program's SweepSpec

    @property
    def T(self) -> int:
        return int(self.traffic["T"])

    @property
    def devices(self) -> int:
        """The devices the sweep splits its seeds over."""
        return int(self.traffic.get("devices", 1))

    @property
    def grid_cells(self) -> int:
        return len(self.grids) * len(self.sim_seeds)

    @property
    def coords(self):
        return [(w, s) for w in self.grids for s in self.sim_seeds]

    def targets(self) -> Tuple[float, float]:
        """The control targets, as the reference works them out."""
        return self.ref.targets(self.dep, self.traffic, self.gen)


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
    ref = bench.reference(cfg["reference"])
    gen = bench.generator(tr["generator"])
    sim = cfg["sim"]
    dep = ref.deployment(sim, tr)
    grid_seed, sim_seeds = seeds_from(seed, int(tr["seeds_per_sweep"]))
    grids = {
        s: gen.make(
            s,
            T=int(tr["T"]),
            m=sim["m"],
            seed=grid_seed,
            N=sim["N"],
            R=int(tr["R"]),
            dt_ms=sim["dt_ms"],
            service_ms=sim["service_ms"],
        )
        for s in tr["scenarios"]
    }
    return Cell(
        name=workload,
        config=cfg,
        traffic=tr,
        ref=ref,
        gen=gen,
        dep=dep,
        grids=grids,
        sim_seeds=sim_seeds,
    )


def _frozen(v):
    """A JSON value as a hashable setting: lists become tuples."""
    return tuple(_frozen(x) for x in v) if isinstance(v, list) else v


def attach_program(cell: Cell) -> Cell:
    """The program's ``SweepSpec`` for the cell, fed the cell's grids:
    ``SimConfig`` from every ``sim`` setting and the traffic file's
    policy stack, each grid field passed to ``Workload`` by name."""
    from repro.core import SimConfig, SweepSpec
    from repro.core.workloads import Workload

    sim, tr = cell.config["sim"], cell.traffic
    known = {f.name for f in dataclasses.fields(SimConfig)}
    unknown = sorted(set(sim) - known)
    if unknown:
        raise ValueError(
            f"{cell.config['name']}: SimConfig has no setting "
            + ", ".join(unknown)
        )
    cfg = SimConfig(
        **{k: _frozen(v) for k, v in sim.items()},
        policy=tr["policy"],
        middleware=tuple(tr["middleware"]),
        controller=tr.get("controller", "hysteresis"),
        fixed_d=int(tr.get("fixed_d", 2)),
    )
    wls = []
    for name, g in cell.grids.items():
        extra = sorted(set(g._fields) - set(Workload._fields))
        if extra:
            raise ValueError(
                f"{tr['generator']}: Workload has no field "
                + ", ".join(extra)
            )
        wls.append(Workload(**g._asdict(), name=name, N=sim["N"]))
    pinned = tr.get("targets")
    cell.spec = SweepSpec(
        config=cfg,
        workloads=tuple(wls),
        policies=(tr["policy"],),
        seeds=cell.sim_seeds,
        metrics="summary",
        devices=cell.devices,
        do_warmup=bool(tr.get("warmup", False)),
        targets=None if pinned is None else tuple(map(float, pinned)),
    )
    return cell


def rows_of(cell: Cell, result) -> Dict[Tuple[str, int], Dict]:
    """The program's summary rows as the reference's field dicts."""
    out = {}
    for w, s in cell.coords:
        r = result.row(workload=w, seed=s)
        out[(w, s)] = {
            f: np.asarray(getattr(r, attr), np.float64)
            for f, attr in cell.ref.FIELDS
        }
    return out
