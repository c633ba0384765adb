"""What the cells read did not move when the harness learned to take a
deployment's reference and generator by name, and the seed-sharded
cell's rows are the unsharded ones."""

import hashlib
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from conftest import ROOT, tiny_cell, write_bench
from midasbench import cell as cell_lib

SEED = 2**31 + 4242

TESTBED = dict(T=40, scenarios=["bursty", "flash_crowd"], seeds_per_sweep=2)
FLEET_SIM = {"m": 16, "P": 16, "N": 20000}
FLEET = dict(T=24, R=64, scenarios=["rename_storm", "job_startup"],
             seeds_per_sweep=2)
# the fleet sweep split over four devices, two seeds on each, cut as far
# as steering still goes by the seed, so that a seed's row is its own
X4_SIM = {"m": 32, "P": 16, "N": 20000}
X4 = dict(T=48, R=96, scenarios=["flash_crowd", "job_startup"],
          seeds_per_sweep=8, devices=4)

# sha256 of the grids and of the reference rows, and the targets, as the
# harness gave them on the CPU before it found references and generators
# by name
DIGESTS = {
    "tiny_testbed": {
        "grids": ("95d69e64c3e4d9c7c9f7f2c04f3962b1"
                  "eb2af71f5385e4340775229188fc73c0"),
        "targets": ["1.0271631939226056", "500.0"],
        "rows": ("a37f0f98c90da98279a391c17215caf6"
                 "4d62c4c32373b95c3d85f45f84ce498a"),
    },
    "tiny_fleet": {
        "grids": ("e8ff716f8b98742117b53a4777f8d24a"
                  "293a3d49782f99cdbf0f1e361a7da0c0"),
        "targets": ["0.5", "400.0"],
        "rows": ("ad0368cd177a4295c44d967ce8198835"
                 "3545c119fd05d1ffa82d0148cd7ec1f4"),
    },
}


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_grids_targets_and_reference_rows_are_as_before(tmp_path, name):
    if name == "tiny_testbed":
        cell = tiny_cell(name, "paper_testbed", "midas_cache_e8",
                         "testbed_midas", **TESTBED)
    else:
        cell = tiny_cell(name, "million_key_fleet", "midas_fleet_e11",
                         "fleet_midas", sim=FLEET_SIM, **FLEET)
    c = cell_lib.build(write_bench(tmp_path, [cell]), name, SEED)
    targets = c.targets()
    rows = []
    for w, s in c.coords:
        r = c.ref.simulate(c.dep, c.grids[w], s, targets)
        rows += [r[f] for f, _ in c.ref.FIELDS]
    assert {
        "grids": _digest([a for g in c.grids.values() for a in g]),
        "targets": [repr(float(t)) for t in targets],
        "rows": _digest(rows),
    } == DIGESTS[name]


SHARDED = """
    import dataclasses, json, sys, time
    sys.path[:0] = [{bench!r}]
    import jax, jax.numpy as jnp
    import run as runner
    from midasbench import cell as cell_lib, check
    from midasbench.spec import Bench
    from repro.core import run_sweep, sweep

    bench = Bench.from_root({root!r})

    def run():
        jax.clear_caches()
        return runner.run(bench, "tiny_x4", {seed}, 0.2, False,
                          t_start=time.perf_counter(), require_chip=False)

    out = run()
    cell = cell_lib.attach_program(cell_lib.build(bench, "tiny_x4", {seed}))
    one = dataclasses.replace(cell.spec, devices=1)
    sharded = cell_lib.rows_of(cell, run_sweep(cell.spec))
    single = cell_lib.rows_of(cell, run_sweep(one))

    # the fault: the other chips' results never gathered, the first
    # chip's block of seeds standing in for each
    real = sweep._run_scan_sweep_sharded

    def first_chip_only(cfg, states, keys, mask, is_write, metrics, n):
        final, outs = real(cfg, states, keys, mask, is_write, metrics, n)
        b = jax.tree_util.tree_leaves(outs)[0].shape[1] // n
        return final, jax.tree_util.tree_map(
            lambda x: jnp.concatenate([x[:, :b]] * n, axis=1), outs)

    first_chip_only.lower = real.lower
    sweep._run_scan_sweep_sharded = first_chip_only
    fault = run()
    firsts = [r for (w, s), r in single.items() if s == cell.sim_seeds[0]]
    print(json.dumps({{
        "correct": out["correct"], "checks": out["checks"],
        "devices": cell.spec.devices,
        "differing": check.rows_differing(single, [sharded]),
        "rows": len(sharded),
        "seeds_alike": sum(
            check.rows_equal(r, f) for f in firsts for r in single.values()
        ),
        "fault_correct": fault["correct"],
        "fault_row_gap": fault["checks"]["row_gap"]["value"],
    }}))
"""


def test_the_sharded_cell_gives_the_unsharded_rows_and_is_correct(tmp_path):
    """A seed-sharded fleet cell on four host devices: its rows equal
    those of ``devices=1`` bitwise, and ``correct`` holds; with the other
    chips' results left out, it does not."""
    cell = tiny_cell("tiny_x4", "million_key_fleet", "midas_fleet_e11",
                     "fleet_midas", sim=X4_SIM, chips=4, **X4)
    # every device's block of seeds in every scenario
    cell["limits"]["sample_cells"] = 2 * 4
    write_bench(tmp_path, [cell])
    code = SHARDED.format(bench=str(ROOT / "bench"), root=str(tmp_path),
                          seed=SEED)
    env = dict(
        os.environ,
        PYTHONPATH=str(ROOT / "src"),
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)], capture_output=True,
        text=True, env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["devices"] == 4 and res["rows"] == 16
    assert res["differing"] == 0
    assert res["seeds_alike"] < res["rows"]  # the seeds change the rows
    assert res["correct"], res["checks"]
    assert not res["fault_correct"] and res["fault_row_gap"] > 1e-4
