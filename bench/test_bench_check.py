"""What decides ``correct``, on a cell small enough for the CPU: a sound
run passes; the control (the reference in bfloat16) and each fault the
cells can have, planted under the timed path, fail; a run off the TPU
and a recompile inside the window fail outright.  A deployment brought
as new files only (its own reference and generator) runs end to end,
and its own reference decides ``correct``."""

import time

import jax
import jax.numpy as jnp
import pytest

import run as runner
from conftest import REAL, tiny_cell, write_bench
from midasbench import cell as cell_lib
from midasbench import check
from repro.core import sim

CELLS = [w["name"] for w in REAL.doc["workloads"]]
SEED = 2**31 + 4242


def _run(bench, workload="tiny_cell", **kw):
    jax.clear_caches()
    try:
        return runner.run(
            bench, workload, SEED, 0.2, False,
            t_start=time.perf_counter(), require_chip=False, **kw
        )
    finally:
        jax.clear_caches()


def test_a_sound_run_is_correct(tiny):
    out = _run(tiny)
    assert out["correct"] and out["failed"] == 0
    assert out["checks"]["row_gap"]["value"] == 0.0
    assert out["attempted"] % 4 == 0 and out["attempted"] >= 4
    assert list(out)[-1] == "checks"


def test_a_traced_run_reports_the_per_layer_metrics(tiny, tmp_path):
    jax.clear_caches()
    out = runner.run(
        tiny, "tiny_cell", SEED, 0.2, True, t_start=time.perf_counter(),
        require_chip=False, cache_dir=tmp_path,
    )
    assert out["correct"]
    assert out["metrics"]["sweep_host_ms"]["value"] > 0
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
def test_the_bfloat16_control_fails_each_cells_limit(tiny, workload):
    limit = REAL.limits(workload)["limits"]["row_gap"]
    cell = cell_lib.build(tiny, "tiny_cell", SEED)
    targets = cell.targets()
    for name, grid in cell.grids.items():
        s = cell.sim_seeds[0]
        ref32 = cell.ref.simulate(cell.dep, grid, s, targets)
        ref16 = cell.ref.simulate(
            cell.dep, grid, s, targets, dtype=jnp.bfloat16
        )
        assert check.row_gap(ref16, ref32)[0] > limit


def _tick_state_unchanged(real):
    def tick(cfg, ring, policy, mws, ctrl, fc, state, inputs):
        _, out = real(cfg, ring, policy, mws, ctrl, fc, state, inputs)
        return state, out

    return tick


def _tick_half_batch(real):
    def tick(cfg, ring, policy, mws, ctrl, fc, state, inputs):
        t, feas, keys, mask, is_write = inputs
        half = mask.at[mask.shape[0] // 2:].set(False)
        return real(cfg, ring, policy, mws, ctrl, fc, state,
                    (t, feas, keys, half, is_write & half))

    return tick


def _summary_altered(real):
    def update(acc, out):
        acc = real(acc, out)
        return acc._replace(arrivals=acc.arrivals + 1.0)

    return update


@pytest.mark.parametrize(
    "target,fault",
    [
        ("_tick", _tick_state_unchanged),
        ("_tick", _tick_half_batch),
        ("_summary_update", _summary_altered),
    ],
    ids=["state_unchanged", "half_batch_left_out", "answer_altered"],
)
def test_a_fault_under_the_timed_path_is_not_correct(
    tiny, monkeypatch, target, fault
):
    monkeypatch.setattr(sim, target, fault(getattr(sim, target)))
    out = _run(tiny)
    assert not out["correct"]
    assert out["failed"] >= 1
    assert out["checks"]["row_gap"]["value"] > 0


def test_a_run_off_the_tpu_fails(tiny):
    with pytest.raises(runner.Failed, match="not a TPU"):
        runner.run(tiny, "tiny_cell", SEED, 0.2, False,
                   t_start=time.perf_counter())


def test_a_recompile_inside_the_window_fails(tiny, monkeypatch):
    import repro.core

    real = repro.core.run_sweep
    calls = []

    def recompiling(spec):
        if calls:
            jax.clear_caches()
        calls.append(1)
        return real(spec)

    monkeypatch.setattr(repro.core, "run_sweep", recompiling)
    with pytest.raises(runner.Failed, match="compiled inside the window"):
        _run(tiny)


# a generator of its own: gen's scenarios and one more
NEW_GEN = """
def steady_renames(p):
    return p.make("skewed", write_frac=0.6)


GENERATORS["steady_renames"] = steady_renames
"""
# the reference's controller starts from d = 3 where the paper says 2
WRONG = ("D_INIT, D_MIN, D_MAX = 2, 1, 4", "D_INIT, D_MIN, D_MAX = 3, 1, 4")


@pytest.mark.parametrize("sound", [True, False], ids=["copy", "wrong_copy"])
def test_a_deployment_of_new_files_runs_and_its_reference_decides(
    tmp_path, sound
):
    queue = (REAL.dir / "reference" / "midas_queue.py").read_text()
    if not sound:
        assert queue.count(WRONG[0]) == 1
        queue = queue.replace(*WRONG)
    gen = (REAL.dir / "traffic_gen" / "gen.py").read_text() + NEW_GEN
    cell = tiny_cell(
        "new_cell", "paper_testbed", "midas_cache_e8", "testbed_midas",
        sim={"N": 2048}, T=40, scenarios=["steady_renames", "bursty"],
        seeds_per_sweep=2, warmup=False, targets=[0.5, 400.0],
        generator="new_gen",
    )
    cell["config"]["reference"] = "new_queue"
    cell["limits"]["sample_cells"] = 2
    bench = write_bench(tmp_path, [cell], files={
        "reference/new_queue.py": queue, "traffic_gen/new_gen.py": gen,
    })
    built = cell_lib.build(bench, "new_cell", SEED)
    assert built.ref is bench.reference("new_queue")
    assert list(built.grids) == ["steady_renames", "bursty"]
    out = _run(bench, "new_cell")
    assert out["correct"] is sound
    assert (out["checks"]["row_gap"]["value"] == 0.0) is sound
    assert out["checks"]["rows_differing"]["value"] == 0


def _seeded_cell(devices, n_seeds, scenarios=("a", "b", "c", "d")):
    return cell_lib.Cell(
        name="sampled", config={}, traffic={"devices": devices}, ref=None,
        gen=None, dep=None, grids=dict.fromkeys(scenarios),
        sim_seeds=tuple(range(100, 100 + n_seeds)),
    )


@pytest.mark.parametrize("n_seeds", [8, 32])
def test_on_one_device_the_sample_is_drawn_from_every_seed(n_seeds):
    import numpy as np

    cell = _seeded_cell(1, n_seeds)
    rng = np.random.default_rng((SEED % 2**64, 1))
    drawn = [
        ("abcd"[i % 4], cell.sim_seeds[int(rng.integers(n_seeds))])
        for i in range(6)
    ]
    assert runner.sample_coords(cell, SEED, 6) == drawn


@pytest.mark.parametrize("n_seeds", [8, 32, 7])
def test_a_sharded_sample_reads_every_devices_block_in_every_scenario(
    n_seeds
):
    # the blocks shard_map gives each device (a short last one padded)
    per = -(-n_seeds // 4)
    block = {s: (s - 100) // per for s in range(100, 100 + n_seeds)}
    cell = _seeded_cell(4, n_seeds)
    got = runner.sample_coords(cell, SEED, 16)
    assert {(w, block[s]) for w, s in got} == {
        (w, b) for w in "abcd" for b in range(-(-n_seeds // per))
    }
