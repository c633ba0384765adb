"""E11 — the sharded million-key sweep (DESIGN.md §12).

The paper's deployment scale — a ~10⁶-key namespace served through a
128-proxy fleet — run through the declarative sweep engine
(:class:`repro.core.sweep.SweepSpec`) with the seed axis sharded over a
device mesh.  Four sections, each a claim from the §12 contract:

* ``parity``   — sharded ``run_sweep`` reproduces the single-device
  nested-vmap results **bit-for-bit** at the full E11 configuration
  (million-key namespace, non-dividing seed count included via the
  seed-axis padding path);
* ``scaling``  — aggregate ticks/s of the SAME total grid (4 scenarios
  × seeds × T) at 1, 2, 4, 8 emulated devices.  Each device count runs
  in its own subprocess because XLA fixes the host device count at
  first init (``--xla_force_host_platform_device_count``).  Honest
  numbers: ``meta.cpus`` records the cores backing the emulated
  devices — emulated devices only speed things up when real cores back
  them, so the ≥2× headline is a multi-core (CI) result;
* ``memory``   — peak host RSS of the identical sweep at R = 10⁵ vs
  R = 10⁶ namespace keys, in fresh subprocesses.  Flat-in-R contract:
  nothing materializes O(R·P); the ratio stays ~1 (midas pin state is
  the only O(R) term, 8 bytes/key/seed);
* ``ring``     — the million-key ring audit: every key resolved
  shard-by-shard from O(m·V/n_shards + tail) subrings
  (``hashring.np_subring``), primaries AND d_max feasible sets
  bit-for-bit equal to the global ring, shards partitioning the keys.

Emits ``experiments/sim/BENCH_shard.json`` incrementally (a CI timeout
still uploads a valid partial artifact) plus CSV rows.  ``--only``
subsets the sections; ``--devices N`` caps the mesh sizes; ``--seeds``
shrinks the grid for smoke runs.
"""
from __future__ import annotations

import json
import os
import resource
import subprocess
import sys
import time
from pathlib import Path
from typing import Optional

import numpy as np

from benchmarks.common import (Artifact, BenchOpts, emit, parse_opts,
                               rows_equal)

ROOT = Path(__file__).resolve().parents[1]

# the E11 grid: paper scale
SCENARIOS = ("bursty", "rename_storm", "flash_crowd", "job_startup")
SEEDS = tuple(range(8))
N_KEYS = 1_000_000     # namespace size R (the paper's ~10⁶ keys)
M = 64                 # metadata servers
V = 64                 # vnodes/server -> 4096-slot ring
P = 128                # proxy fleet (one routing wave per proxy)
R_SLOTS = 512          # request slots per tick
T = 240                # 12 s at dt=50 ms
DEVICES = (1, 2, 4, 8)
N_SHARDS = 8           # subring arcs for the ring audit
D_MAX = 4
MEM_NS = (100_000, 1_000_000)
# §III-B targets pinned (warmup at million-key scale is a separate
# experiment; E11 measures the sweep engine, not the warmup)
TARGETS = (0.5, 400.0)
SECTIONS = ("parity", "scaling", "memory", "ring")
_TAG = "E11-RESULT "
# what every worker result line carries about where it ran
_ENV_KEYS = ("jax_version", "platform", "device_kind")


def fleet_spec(n=N_KEYS, t=T, seeds=SEEDS, devices=1, scenarios=SCENARIOS):
    from repro.core import SimConfig, SweepSpec, make_workload

    wls = tuple(
        make_workload(s, T=t, m=M, seed=0, N=n, R=R_SLOTS)
        for s in scenarios
    )
    cfg = SimConfig(
        m=M,
        N=n,
        V=V,
        P=P,
        policy="midas",
        fleet_routing=True,
        gossip_ms=100.0,
    )
    return SweepSpec(
        config=cfg,
        workloads=wls,
        policies=("midas",),
        seeds=seeds,
        metrics="summary",
        devices=devices,
        do_warmup=False,
        targets=TARGETS,
    )


def _rss_mb() -> float:
    """Peak RSS of this process in MB (ru_maxrss is KB on Linux)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


# --------------------------------------------------------------------------
# Worker modes (run in subprocesses so each gets its own device count)
# --------------------------------------------------------------------------


def _worker(req: dict) -> dict:
    import jax

    from repro.core import run_sweep
    from repro.core.sweep import _SHARD_TRACES

    seeds = tuple(range(req["seeds"]))
    if req["mode"] == "scaling":
        spec = fleet_spec(
            n=req["n"], t=req["t"], seeds=seeds, devices=req["devices"]
        )
        t0 = time.perf_counter()
        run_sweep(spec)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = run_sweep(spec)
        run_s = time.perf_counter() - t0
        ticks = len(spec.workloads) * len(seeds) * req["t"]
        # windowing contract rides the subprocess result line (the
        # block is plain JSON); first scenario stands for the grid
        from repro.obs import windows

        cell = windows.cell_block(
            res.rows(policy="midas", workload=SCENARIOS[0])
        )
        return {
            "devices": req["devices"],
            "visible_devices": len(jax.devices()),
            "cells": spec.n_cells,
            "first_call_s": round(compile_s, 2),
            "run_s": round(run_s, 3),
            "ticks": ticks,
            "ticks_per_s": round(ticks / run_s, 1),
            "key_slots_per_s": round(ticks * R_SLOTS / run_s),
            "rss_mb": round(_rss_mb(), 1),
            "rows": len(res.cells),
            **cell,
        }
    if req["mode"] == "parity":
        n_dev = req["devices"]
        single = run_sweep(
            fleet_spec(n=req["n"], t=req["t"], seeds=seeds, devices=1)
        )
        sharded = run_sweep(
            fleet_spec(n=req["n"], t=req["t"], seeds=seeds, devices=n_dev)
        )
        ok = set(single.cells) == set(sharded.cells) and all(
            rows_equal(single.cells[c], sharded.cells[c])
            for c in single.cells
        )
        return {
            "devices": n_dev,
            "seeds": len(seeds),
            "padded": bool(len(seeds) % n_dev),
            "cells": len(single.cells),
            "bitwise_equal": bool(ok),
            "shard_traces": _SHARD_TRACES[0],
        }
    if req["mode"] == "memory":
        spec = fleet_spec(
            n=req["n"],
            t=req["t"],
            seeds=seeds,
            scenarios=SCENARIOS[:1],
            devices=req["devices"],
        )
        run_sweep(spec)
        return {"n": req["n"], "rss_mb": round(_rss_mb(), 1)}
    raise ValueError(f"unknown worker mode {req['mode']!r}")


def _launch(req: dict, devices: int, meta: dict) -> dict:
    """Run one worker in a fresh subprocess with its own device count
    (XLA locks the host platform device count at first jax init), and
    record in ``meta`` the stack and device it reports it ran on."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "benchmarks.shard_sweep",
            "--worker",
            json.dumps(req),
        ],
        cwd=ROOT,
        env=env,
        capture_output=True,
        text=True,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"shard_sweep worker {req['mode']!r} failed "
            f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}"
        )
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith(_TAG):
            res = json.loads(line[len(_TAG):])
            for key in _ENV_KEYS:
                meta[key] = res.pop(key)
            return res
    raise RuntimeError(
        f"shard_sweep worker {req['mode']!r} produced no result line"
    )


# --------------------------------------------------------------------------
# Ring audit (pure numpy — no devices involved)
# --------------------------------------------------------------------------


def _ring_audit(n_keys: int) -> dict:
    from repro.core import hashring

    rng = np.random.default_rng(0)
    keys = rng.integers(0, 1 << 31, size=n_keys, dtype=np.int64)
    shard_of = hashring.np_key_shard(keys, N_SHARDS)
    # global reference = the single-shard "subring" (whole position
    # space + tail), so reference and per-shard paths share one code path
    whole = hashring.np_subring(M, V, 0, 1)
    primaries_ok = feasible_ok = True
    covered = 0
    max_sub = 0
    for s in range(N_SHARDS):
        sub = hashring.np_subring(M, V, s, N_SHARDS)
        max_sub = max(max_sub, sub.positions.size)
        ks = keys[shard_of == s]
        covered += ks.size
        if not np.array_equal(
            hashring.np_subring_primary(sub, ks),
            hashring.np_subring_primary(whole, ks),
        ):
            primaries_ok = False
        if not np.array_equal(
            hashring.np_subring_feasible(sub, ks, D_MAX),
            hashring.np_subring_feasible(whole, ks, D_MAX),
        ):
            feasible_ok = False
    return {
        "n_keys": n_keys,
        "m": M,
        "V": V,
        "n_shards": N_SHARDS,
        "d_max": D_MAX,
        "shards_partition_keys": bool(covered == n_keys),
        "primaries_bitwise_equal": bool(primaries_ok),
        "feasible_sets_bitwise_equal": bool(feasible_ok),
        "global_ring_slots": int(whole.positions.size),
        "max_subring_slots": int(max_sub),
        "subring_memory_ratio": round(max_sub / whole.positions.size, 4),
    }


# --------------------------------------------------------------------------
# Orchestrator
# --------------------------------------------------------------------------


def run(opts: Optional[BenchOpts] = None) -> None:
    opts = opts or BenchOpts()
    sections = opts.pick(SECTIONS, "sections")
    n_seeds = len(opts.seeds(SEEDS))
    devs = DEVICES
    if opts.devices > 1:
        devs = tuple(sorted({1, opts.devices}))
    devs = tuple(d for d in devs if d <= (os.cpu_count() or 1) * 8)
    # this process never touches JAX: each worker needs the device to
    # itself, and the stack + device in the artifact are the ones the
    # workers report (_launch)
    art = Artifact("BENCH_shard.json", opts.out)
    doc: dict = {
        "meta": {
            "cpus": os.cpu_count(),
            "n_keys": N_KEYS,
            "m": M,
            "V": V,
            "P": P,
            "r_slots": R_SLOTS,
            "T": T,
            "scenarios": list(SCENARIOS),
            "seeds": n_seeds,
            "device_counts": list(devs),
        },
    }
    art.write(doc)

    if "parity" in sections:
        n_dev = max(d for d in devs if d > 1) if len(devs) > 1 else 2
        # seed count chosen to NOT divide the mesh -> exercises padding
        res = _launch(
            {
                "mode": "parity",
                "n": N_KEYS,
                "t": max(T // 4, 8),
                "seeds": max(n_dev - 1, 2),
                "devices": n_dev,
            },
            devices=n_dev,
            meta=doc["meta"],
        )
        doc["parity"] = res
        art.write(doc)
        emit(
            "shard_sweep/parity",
            0.0,
            f"bitwise_equal={res['bitwise_equal']} "
            f"devices={res['devices']} cells={res['cells']} "
            f"padded={res['padded']}",
        )

    if "scaling" in sections:
        doc["scaling"] = {}
        base = None
        for d in devs:
            res = _launch(
                {
                    "mode": "scaling",
                    "n": N_KEYS,
                    "t": T,
                    "seeds": n_seeds,
                    "devices": d,
                },
                devices=d,
                meta=doc["meta"],
            )
            if base is None:
                base = res["run_s"]
            res["speedup_vs_1dev"] = round(base / res["run_s"], 2)
            doc["scaling"][str(d)] = res
            art.write(doc)
            emit(
                f"shard_sweep/scaling/{d}dev",
                res["run_s"] * 1e6,
                f"ticks/s={res['ticks_per_s']:,.0f} "
                f"speedup={res['speedup_vs_1dev']}x "
                f"rss={res['rss_mb']:.0f}MB",
            )

    if "memory" in sections:
        doc["memory"] = {"runs": []}
        rss = []
        for n in MEM_NS:
            res = _launch(
                {
                    "mode": "memory",
                    "n": n,
                    "t": max(T // 2, 8),
                    "seeds": min(n_seeds, 2),
                    "devices": 1,
                },
                devices=1,
                meta=doc["meta"],
            )
            rss.append(res["rss_mb"])
            doc["memory"]["runs"].append(res)
            art.write(doc)
        ratio = rss[-1] / max(rss[0], 1e-9)
        doc["memory"]["peak_rss_ratio"] = round(ratio, 3)
        doc["memory"]["flat_in_R"] = bool(ratio < 1.5)
        art.write(doc)
        emit(
            "shard_sweep/memory",
            0.0,
            f"rss@{MEM_NS[0]}={rss[0]:.0f}MB "
            f"rss@{MEM_NS[-1]}={rss[-1]:.0f}MB "
            f"ratio={ratio:.2f} flat={doc['memory']['flat_in_R']}",
        )

    if "ring" in sections:
        doc["ring_audit"] = _ring_audit(N_KEYS)
        art.write(doc)
        ra = doc["ring_audit"]
        emit(
            "shard_sweep/ring_audit",
            0.0,
            f"keys={ra['n_keys']:,} "
            f"primaries_ok={ra['primaries_bitwise_equal']} "
            f"feasible_ok={ra['feasible_sets_bitwise_equal']} "
            f"subring_mem={ra['subring_memory_ratio']:.3f}x",
        )


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv[:1] == ["--worker"]:
        import jax

        out = _worker(json.loads(argv[1]))
        dev = jax.devices()[0]
        env = (jax.__version__, dev.platform, dev.device_kind)
        out.update(zip(_ENV_KEYS, env))
        print(_TAG + json.dumps(out), flush=True)
        return
    run(
        parse_opts(
            argv,
            prog="benchmarks.shard_sweep",
            description=__doc__.splitlines()[0],
            axis="sections",
        )
    )


if __name__ == "__main__":
    main()
