"""The benchmark's traffic generators: seeded, and shaped as the
traffic files say."""

import json
from pathlib import Path

import numpy as np
import pytest

from midasbench import cell as cell_lib
from midasbench.spec import Bench

ROOT = Path(__file__).resolve().parent.parent
BENCH = Bench.from_root(ROOT)
WORKLOADS = [w["name"] for w in BENCH.doc["workloads"]]
GEN = BENCH.generator("gen")


@pytest.mark.parametrize("name", sorted(GEN.GENERATORS))
def test_one_seed_gives_the_same_grid_twice(name):
    kw = dict(T=300, m=8, N=4096, seed=2**31 + 17)
    a, b = GEN.make(name, **kw), GEN.make(name, **kw)
    other = GEN.make(name, **dict(kw, seed=kw["seed"] + 1))
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a.keys), np.asarray(other.keys))


@pytest.mark.parametrize("workload", WORKLOADS)
def test_grids_match_the_traffic_file(workload):
    w = BENCH.workload(workload)
    tr = BENCH.traffic(w["traffic"])
    cfg = BENCH.config(w["config"])
    cell = cell_lib.build(BENCH, workload, seed=987654321987)
    assert list(cell.grids) == tr["scenarios"]
    assert len(set(cell.sim_seeds)) == tr["seeds_per_sweep"]
    for g in cell.grids.values():
        keys, mask, is_write = (np.asarray(a) for a in g)
        assert keys.shape == mask.shape == (tr["T"], tr["R"])
        assert keys.min() >= 0 and keys.max() < cfg["sim"]["N"]
        assert not (is_write & ~mask).any()
        assert mask.any()


def test_run_seeds_name_distinct_sweeps():
    g1, s1 = cell_lib.seeds_from(2**31 + 5, 8)
    g2, s2 = cell_lib.seeds_from(2**31 + 6, 8)
    assert (g1, s1) == cell_lib.seeds_from(2**31 + 5, 8)
    assert (g1, s1) != (g2, s2)
    assert len(set(s1)) == 8 and max(s1) < 2**31


def test_traffic_files_are_named_as_their_file():
    for path in sorted((ROOT / "bench" / "traffic").glob("*.json")):
        assert json.loads(path.read_text())["name"] == path.stem
