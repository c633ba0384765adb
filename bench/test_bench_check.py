"""What decides ``correct``, on a cell small enough for the CPU: a sound
run passes; the control (the reference in bfloat16) and each fault the
cells can have, planted under the timed path, fail; a run off the TPU
and a recompile inside the window fail outright."""

import json
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import run as runner
from midasbench import cell as cell_lib
from midasbench import check, reference
from midasbench.spec import Bench
from repro.core import sim

ROOT = Path(__file__).resolve().parent.parent
REAL = Bench.from_root(ROOT)
CELLS = [w["name"] for w in REAL.doc["workloads"]]
SEED = 2**31 + 4242


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A copy of the testbed cell cut to 40 ticks, two scenarios and two
    seeds per sweep, with pinned targets, and the metric readers."""
    root = tmp_path_factory.mktemp("tiny")
    d = root / "bench"
    for sub in ("configs", "traffic", "limits"):
        (d / sub).mkdir(parents=True)
    for sub in ("metrics", "cost"):
        shutil.copytree(REAL.dir / sub, d / sub)
    cfg = REAL.config("paper_testbed")
    (d / "configs" / "paper_testbed.json").write_text(json.dumps(cfg))
    tr = REAL.traffic("midas_cache_e8")
    tr.update(T=40, scenarios=["bursty", "flash_crowd"], seeds_per_sweep=2,
              warmup=False, targets=[0.5, 400.0])
    (d / "traffic" / "tiny.json").write_text(json.dumps(tr))
    lim = REAL.limits("testbed_midas")
    lim["sample_cells"] = 2
    (d / "limits" / "tiny_cell.json").write_text(json.dumps(lim))
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["workloads"] = [{"name": "tiny_cell", "config": "paper_testbed",
                         "traffic": "tiny", "chips": 1, "why": "test"}]
    for mt in doc["end_to_end"] + doc["per_layer"]:
        mt.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return Bench(doc, d)


def _run(bench, **kw):
    jax.clear_caches()
    try:
        return runner.run(
            bench, "tiny_cell", SEED, 0.2, False,
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
    targets = cell_lib.reference_targets(cell)
    for name, grid in cell.grids.items():
        s = cell.sim_seeds[0]
        ref32 = reference.simulate(cell.dep, grid, s, targets)
        ref16 = reference.simulate(
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
