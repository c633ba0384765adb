"""Readings that the limits of ``correct`` are set from; not part of a
benchmark run.

    python bench/control.py --workload <cell> --seeds 11,12,13 [--out FILE]

For each seed, in one process: the cell's sweep runs once through the
program, and the grid cells that a run with that seed samples are
simulated by the plain reference in float32 (the precision the
deployment states) and in bfloat16 (the control: the reference in the
nearest lower precision, standing in for the program).  It prints, per
seed, the program's ``row_gap`` against the float32 reference (a sound
reading) and the control's (which the limit has to reject), and writes
them as JSON to ``--out``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def readings(bench, workload: str, seeds, log=print):
    import jax.numpy as jnp

    from midasbench import cell as cell_lib
    from midasbench import check
    from repro.core import run_sweep
    from run import sample_coords

    k = int(bench.limits(workload)["sample_cells"])
    out = []
    for seed in seeds:
        cell = cell_lib.attach_program(cell_lib.build(bench, workload, seed))
        t0 = time.perf_counter()
        rows = cell_lib.rows_of(cell, run_sweep(cell.spec))
        t1 = time.perf_counter()
        cell.spec = None
        targets = cell.targets()
        sound, control = [], []
        for w, s in sample_coords(cell, seed, k):
            grid = cell.grids[w]
            ref32 = cell.ref.simulate(cell.dep, grid, s, targets)
            ref16 = cell.ref.simulate(
                cell.dep, grid, s, targets, dtype=jnp.bfloat16
            )
            sound.append(check.row_gap(rows[(w, s)], ref32))
            control.append(check.row_gap(ref16, ref32))
        rec = {
            "seed": seed,
            "program_row_gap": max(g for g, _ in sound),
            "control_row_gap": min(g for g, _ in control),
            "control_fields": sorted({f for _, f in control}),
            "sweep_s": t1 - t0,
            "reference_s": time.perf_counter() - t1,
        }
        log(json.dumps(rec))
        out.append(rec)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_cache" / "tpu"))
    import jax

    from run import use_compile_cache

    use_compile_cache(jax)
    from midasbench.spec import Bench

    recs = readings(
        Bench.from_root(ROOT),
        args.workload,
        [int(s) for s in args.seeds.split(",")],
    )
    summary = {
        "workload": args.workload,
        "device": jax.devices()[0].device_kind,
        "program_row_gap_max": max(r["program_row_gap"] for r in recs),
        "control_row_gap_min": min(r["control_row_gap"] for r in recs),
        "seeds": recs,
    }
    print(json.dumps(summary), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
