"""The plain reference of the checkpoint-storm deployment with a server
crash (``reference/midas_faults.py``) against the program, on the CPU at
a small size: the same semantics give the same rows, the same schedule
tick for tick, and a reference that leaves out part of the fault
semantics, or computes in bfloat16, is rejected by the comparison that
decides ``correct``.  It refuses, naming it, what it does not model."""

import dataclasses
import re
import time

import jax.numpy as jnp
import numpy as np
import pytest

import run as runner
from conftest import REAL, tiny_cell, write_bench
from midasbench import cell as cell_lib
from midasbench import check
from midasbench.spec import _load_module
from repro.core import SimConfig, faults, hashring, run_sweep

SEED = 2**31 + 4242
CONFIG = "million_key_fleet_ckpt_crash"
CELL = "fleet_ckpt_crash"
EVENTS = tuple(REAL.config(CONFIG)["sim"]["faults"])
CRASH = EVENTS[1:]  # the crash alone, without the storm
SMALL = {"m": 8, "P": 16, "N": 4096}
LIMIT = REAL.limits(CELL)["limits"]["row_gap"]
SCENARIOS = REAL.traffic("midas_fleet_e11")["scenarios"]


def _bench(tmp_path_factory, name, events):
    cell = tiny_cell(name, CONFIG, "midas_fleet_e11", CELL,
                     sim=dict(SMALL, faults=list(events)), R=64,
                     seeds_per_sweep=2)
    return write_bench(tmp_path_factory.mktemp(name), [cell])


class Ran:
    """A tiny cell's bench, its cell and the program's rows."""

    def __init__(self, bench, name):
        self.bench = bench
        self.cell = cell_lib.attach_program(
            cell_lib.build(bench, name, SEED)
        )
        self.rows = cell_lib.rows_of(self.cell, run_sweep(self.cell.spec))
        self.targets = self.cell.targets()

    def gap(self, ref, w, s, **kw):
        """Row gap of ``ref``'s row of grid cell (w, s) to the
        program's."""
        dep = ref.deployment(self.cell.config["sim"], self.cell.traffic)
        row = ref.simulate(dep, self.cell.grids[w], s, self.targets, **kw)
        return check.row_gap(self.rows[(w, s)], row)[0]


@pytest.fixture(scope="module")
def storm_crash(tmp_path_factory):
    """The cell at m=8, P=16, N=4096, R=64, T=240, two seeds per sweep,
    its own fault program."""
    return Ran(_bench(tmp_path_factory, "tiny_crash", EVENTS), "tiny_crash")


@pytest.fixture(scope="module")
def crash_only(tmp_path_factory):
    """The same cell under the crash alone."""
    return Ran(_bench(tmp_path_factory, "tiny_crash_only", CRASH),
               "tiny_crash_only")


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("program", ["storm_crash", "crash_only"])
def test_the_programs_rows_are_the_references(request, program, scenario):
    ran = request.getfixturevalue(program)
    for s in ran.cell.sim_seeds:
        assert ran.gap(ran.cell.ref, scenario, s) <= LIMIT


PROGRAMS = {
    "ckpt_crash": EVENTS,
    "crash_only": CRASH,
    "storm_only": EVENTS[:1],
    # over before its detection: one epoch, a dead undetected server
    "short_crash": ("proxy_crash:t0=30,duration=6,target=2",),
    # to the end of the horizon, by default on the last server
    "open_crash": ("proxy_crash:t0=200,duration=0",),
    "two_crashes": ("proxy_crash:t0=20,duration=60,target=1",
                    "proxy_crash:t0=50,duration=100,target=5",
                    "ckpt_storm_fleet:t0=40,duration=30,magnitude=0.2",
                    "ckpt_storm_fleet:t0=60,duration=60,magnitude=0.5"),
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_the_references_schedule_is_the_programs_tick_for_tick(
    storm_crash, program
):
    c = storm_crash.cell
    sim = dict(c.config["sim"], faults=list(PROGRAMS[program]))
    dep = c.ref.deployment(sim, c.traffic)
    cfg = SimConfig(**{k: sim[k] for k in ("m", "P", "N", "V", "dt_ms",
                                           "d_max")},
                    faults=PROGRAMS[program])
    T = c.T
    fc = faults.compile_faults(cfg, T)
    sch, flip, moved = c.ref._program(dep, T)
    np.testing.assert_array_equal(sch.member, fc.member)
    np.testing.assert_array_equal(sch.detected, fc.detected)
    np.testing.assert_array_equal(sch.epoch, fc.epoch)
    np.testing.assert_array_equal(sch.masks, fc.epoch_masks)
    np.testing.assert_array_equal(sch.avail, fc.avail)
    np.testing.assert_array_equal(sch.storm, fc.storm)
    assert sch.scan_width == fc.scan_width
    # the flips and the keys each moves
    assert (flip >= 0).tolist() == (fc.epoch != fc.epoch_prev).tolist()
    for t in np.flatnonzero(flip >= 0):
        own = fc.owner_by_epoch
        np.testing.assert_array_equal(
            moved[flip[t]], own[fc.epoch[t]] != own[fc.epoch_prev[t]]
        )
    # the storm overlay and each tick's feasible sets, on a grid
    g = c.grids["bursty"]
    keys, mask, is_write = (np.asarray(a) for a in g)
    want = faults.apply_traffic(fc, jnp.asarray(keys), jnp.asarray(mask),
                                jnp.asarray(is_write))
    got = c.ref.storm_overlay(sch.storm, keys, mask, is_write)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, np.asarray(b))
    ring = hashring.make_ring(cfg.m, cfg.V)
    want = faults.feasible_by_epoch(ring, jnp.asarray(got[0]), cfg.d_max,
                                    fc)
    if moved.shape[0]:
        feas = np.stack([
            c.ref.live_feasible(dep, got[0], mk, sch.scan_width)
            for mk in sch.masks
        ])[sch.epoch, np.arange(T)]
    else:
        feas = c.ref.mq.feasible(dep, got[0])
    np.testing.assert_array_equal(feas, np.asarray(want))


# each planted fault: a line of the reference replaced, and the fault
# program it is compared under.  Under the storm the write-pressure
# guard stops the cache installing from the storm's first ticks to the
# horizon's end, so the availability guard, which only adds to it,
# changes the rows under the crash alone; the remap reaches the rows
# through ``remap_dropped`` under the cell's own program.
PLANTS = {
    "no_remap_invalidation": (
        "    if several:\n        s, dropped = jax.lax.cond(",
        "    if False:\n        s, dropped = jax.lax.cond(",
        "storm_crash",
    ),
    "no_install_guard": (
        "| (\n        avail < AVAIL_FULL\n    )",
        "| (\n        avail < -1.0\n    )",
        "crash_only",
    ),
    "instant_detection": (
        "    detected = detect(member, K)\n",
        "    detected = member.copy()\n",
        "storm_crash",
    ),
}


@pytest.mark.parametrize("plant", sorted(PLANTS) + ["bfloat16"])
def test_a_reference_without_part_of_the_semantics_is_rejected(
    request, tmp_path, plant
):
    if plant == "bfloat16":
        ran = request.getfixturevalue("storm_crash")
        ref, kw = ran.cell.ref, {"dtype": jnp.bfloat16}
    else:
        old, new, fixture = PLANTS[plant]
        ran = request.getfixturevalue(fixture)
        src = (REAL.dir / "reference" / "midas_faults.py").read_text()
        assert src.count(old) == 1
        path = tmp_path / "reference" / f"planted_{plant}.py"
        path.parent.mkdir()
        path.write_text(src.replace(old, new))
        (tmp_path / "reference" / "midas_queue.py").write_text(
            (REAL.dir / "reference" / "midas_queue.py").read_text()
        )
        ref, kw = _load_module(path), {}
    s = ran.cell.sim_seeds[0]
    gaps = [ran.gap(ref, w, s, **kw) for w in SCENARIOS]
    assert max(gaps) > LIMIT, gaps


@pytest.mark.parametrize(
    "change,culprit",
    [
        ({"faults": ["server_brownout:target=1,magnitude=0.5"]},
         "server_brownout"),
        ({"faults": ["gossip_partition:t0=10"]}, "gossip_partition"),
        ({"faults": ["proxy_join:t0=10,target=2"]}, "proxy_join"),
        ({"faults": [{"trigger": "proxy_crash", "effect":
                      "gossip_partition"}]}, "no cascade"),
        ({"faults": ["proxy_crash:t0=80,when=3"]}, "when=3"),
        ({"faults": ["proxy_crash:target=8"]}, "target=8"),
        ({"faults": []}, "sim.faults"),
        ({"fleet_routing": False}, "sim.fleet_routing=False"),
        ({"cache_mode": "ttl_per_key"}, "sim.cache_mode='ttl_per_key'"),
        ({"policy": "power_of_d"}, "policy='power_of_d'"),
        ({"middleware": ["cache"]}, "middleware=['cache']"),
    ],
    ids=["brownout", "partition", "join", "cascade", "unknown_key",
         "target", "no_faults", "routing", "cache_mode", "policy",
         "middleware"],
)
def test_the_reference_refuses_what_it_does_not_model_naming_it(
    storm_crash, change, culprit
):
    c = storm_crash.cell
    sim = dict(c.config["sim"], **{k: v for k, v in change.items()
                                   if k not in ("policy", "middleware")})
    traffic = dict(c.traffic, **{k: v for k, v in change.items()
                                 if k in ("policy", "middleware")})
    with pytest.raises(ValueError, match=re.escape(culprit)) as e:
        c.ref.deployment(sim, traffic)
    assert "midas_faults" in str(e.value)


def test_the_tiny_cell_runs_end_to_end_and_is_correct(storm_crash):
    out = runner.run(
        storm_crash.bench, "tiny_crash", SEED, 0.2, False,
        t_start=time.perf_counter(), require_chip=False,
    )
    assert out["correct"] and out["failed"] == 0
    assert out["checks"]["row_gap"]["value"] <= LIMIT
    assert out["checks"]["rows_differing"]["value"] == 0
    assert list(out["metrics"]) == ["cell_ticks_per_s", "setup_s",
                                    "peak_hbm_mb"]


def test_the_fault_reader_splits_the_owner_diff_from_the_remap(
    storm_crash, monkeypatch
):
    """On hand-made operations: the fault phase is the union of its ops,
    and the ``faults:`` line splits it into ``inval`` and ``remap`` and
    gives the counters of the cell's compiled fault program.  Where no
    op ran in the phase, as in a fault-free program, it gives nothing
    and writes no line."""
    from midasbench import tracecalc as tc
    from repro.obs import trace as obs

    # one unit of time per tick of the cell's 240 is a microsecond
    us, mod = 240 * 1000.0 / 10, "jit__run_scan_sweep(3)"
    ops = [
        tc.Op("while.1", 0, 100 * us, mod),
        tc.Op("fusion.2", 10 * us, 12 * us, mod),
        tc.Op("fusion.3", 12 * us, 20 * us, mod),
        tc.Op("copy.4", 14 * us, 18 * us, mod),  # inside fusion.3
        tc.Op("fusion.5", 20 * us, 40 * us, mod),
    ]
    pmap = {
        "fusion.2": ("tick/faults", "inval"),
        "fusion.3": ("tick/faults", "remap"),
        "copy.4": ("tick/faults", "remap"),
        "fusion.5": ("tick/route", ""),
    }
    monkeypatch.setattr(obs, "phase_map",
                        lambda: {"jit__run_scan_sweep": pmap})
    cell = storm_crash.cell
    fc = faults.compile_faults(cell.spec.config, cell.T)
    hits = faults.base._compile_cached.cache_info().hits
    moved = faults.counters(fc, cell.traffic["R"])["moved_keys"]

    def ctx(ops):
        return runner.Ctx(
            trace=tc.Trace({"/device:TPU:0": ops},
                           [tc.Span("bench/window", 0, 200 * us)]),
            lo=0, hi=200 * us, spans=[], n_sweeps=1,
            # as in a run, the sweep's spec is gone when readers run
            cell=dataclasses.replace(cell, spec=None), bench=REAL,
            peaks={}, devices=1, notes=[],
        )

    reader = REAL.reader("faults_us_per_tick")
    c = ctx(ops)
    assert reader.read(c) == pytest.approx(1.0)
    (line,) = c._notes
    assert line.startswith("faults: tick/faults 1.0 us/tick")
    assert "inval 0.2" in line and "remap 0.8" in line
    assert "epochs 3" in line and "flip_ticks [90, 180]" in line
    assert f"moved_keys {moved}" in line and moved[0] > 0
    # the reader's config is the sweep's: the compiled program is reused
    assert faults.base._compile_cached.cache_info().hits > hits
    c = ctx([o for o in ops if pmap.get(o.name, ("",))[0] != "tick/faults"])
    assert reader.read(c) is None and c._notes == []
