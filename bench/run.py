"""Run one cell of the benchmark once, on the chip it starts on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) is a deployment
under a sweep grid.  The run builds the grid from ``--seed``, warms up
by running the cell's own sweep once through ``repro.core.run_sweep``
(set-up: imports, grid generation, compilation or a compile-cache load),
then calls ``run_sweep`` back to back for ``--seconds`` and reports the
simulated cell-ticks per second over all that work.  With ``--trace 1``
it instead traces one or two sweeps (``traced_sweeps`` in the traffic
file) with the profiler and reports the
per-layer metrics.  It then checks the window's rows: every sweep's rows
must equal the first sweep's, and a sample of grid cells drawn from the
seed must match the plain reference that the cell's configuration names
(``reference/<name>.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted`` (grid cells simulated in the window), ``failed``,
``metrics``, ``device`` and, last, ``checks`` (each compared number with
its limit).  The run exits non-zero without that line when JAX finds no
TPU or fewer chips than the cell needs, when the program is not in the
checkout, or when a program recompiles inside the window.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Failed(RuntimeError):
    """The run cannot produce a result."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def use_compile_cache(jax) -> None:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else a fixed directory inside the checkout."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".bench_cache" / "jax"
    )
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Ctx:
    """What a per-layer metric reader may read: the reduced trace of
    the traced window, the program's spans in it, and the cell."""

    def __init__(self, *, trace, lo, hi, spans, n_sweeps, cell, bench,
                 peaks, devices, notes):
        from midasbench import tracecalc

        self._tc = tracecalc
        self.trace, self.lo, self.hi = trace, lo, hi
        self.window_ns = hi - lo
        self.spans = spans
        self.n_sweeps = n_sweeps
        self.cell = cell
        self.peaks = peaks
        self.devices = devices
        self._bench = bench
        self._notes = notes

    def busy_ns(self, select=None) -> float:
        return self._tc.busy_ns(self.trace, self.lo, self.hi, select)

    def kernel_ns(self, kernel: str):
        """(device ns, calls) of the operations named after ``kernel``."""
        return self._tc.op_time_ns(
            self.trace, self.lo, self.hi, lambda op: kernel in op.name
        )

    def cost(self, kernel: str):
        return self._bench.cost(kernel)

    def note(self, msg: str) -> None:
        self._notes.append(msg)


def sample_coords(cell, seed: int, k: int):
    """``k`` grid cells drawn from the seed, one scenario after another.

    A sweep over several devices splits its seeds into one block per
    device, in order, as ``shard_map`` does; each scenario's turns then
    go to the blocks in turn, so that ``k`` of scenarios × devices reads
    every device's results in every scenario.  On one device each seed
    is drawn from all of them."""
    import numpy as np

    rng = np.random.default_rng((int(seed) % 2**64, 1))
    names = list(cell.grids)
    seeds = cell.sim_seeds
    per = -(-len(seeds) // cell.devices)
    blocks = [seeds[i:i + per] for i in range(0, len(seeds), per)]
    out = []
    for i in range(k):
        block = blocks[(i // len(names)) % len(blocks)]
        out.append((names[i % len(names)], block[int(rng.integers(
            len(block)))]))
    return out


def timed_window(run_sweep, spec, seconds: float):
    """``run_sweep`` back to back until ``seconds`` have passed; the
    results and the time to the end of the last sweep."""
    t0 = time.perf_counter()
    results, ends = [], []
    while not ends or ends[-1] - t0 < seconds:
        results.append(run_sweep(spec))
        ends.append(time.perf_counter())
    per = [b - a for a, b in zip([t0] + ends, ends)]
    log(f"window: {len(per)} sweeps of {min(per):.4f}-{max(per):.4f} s "
        f"(first {per[0]:.4f} s)")
    return results, ends[-1] - t0


def traced_window(jax, run_sweep, spec, sweeps: int, tdir: Path):
    """``sweeps`` sweeps under the profiler, the program's spans on its
    clock; the results, the spans and the trace file."""
    from repro.obs import trace as obs

    shutil.rmtree(tdir, ignore_errors=True)
    # device ops and annotated host spans only: no Python-call tracing
    # (it slows the host the window measures) and no HLO protos
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    obs.RECORDER.configure(profile=True, fresh=True)
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    results = []
    try:
        with jax.profiler.TraceAnnotation("bench/window"):
            for _ in range(sweeps):
                with jax.profiler.TraceAnnotation("bench/sweep"):
                    results.append(run_sweep(spec))
    finally:
        jax.profiler.stop_trace()
        obs.RECORDER.configure(profile=False)
    spans = [e for e in obs.RECORDER.events if e.get("ph") == "X"]
    xplanes = sorted(tdir.glob("**/*.xplane.pb"))
    if not xplanes:
        raise Failed("the profiler wrote no trace")
    return results, spans, xplanes[-1]


def check_outputs(cell, seed: int, limits, first, results):
    """The compared numbers, and how many compared rows failed: every
    window sweep's rows against the warm-up sweep's, and the last
    sweep's sampled rows against the plain reference, which runs after
    the program's device state is freed."""
    from midasbench import cell as cell_lib
    from midasbench import check

    first_rows = cell_lib.rows_of(cell, first)
    later_rows = [cell_lib.rows_of(cell, r) for r in results]
    cell.spec = None
    del first, results
    gc.collect()
    t_ref = time.perf_counter()
    targets = cell.targets()
    gaps = []
    for w, s in sample_coords(cell, seed, int(limits["sample_cells"])):
        ref_row = cell.ref.simulate(cell.dep, cell.grids[w], s, targets)
        gap, field = check.row_gap(later_rows[-1][(w, s)], ref_row)
        gaps.append(gap)
        log(f"reference {w} seed {s}: gap {gap!r} (widest in {field})")
    log(f"reference check took {time.perf_counter() - t_ref:.3f} s")
    numbers = {
        "row_gap": max(gaps),
        "rows_differing": check.rows_differing(first_rows, later_rows),
    }
    failed = sum(g > limits["limits"]["row_gap"] for g in gaps)
    return numbers, failed + int(numbers["rows_differing"])


def run(bench, workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True, cache_dir: Path = None):
    """One run of one cell; returns the result object."""
    import jax

    chips = int(bench.workload(workload)["chips"])
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise Failed(
            f"JAX's first device is {devs[0].platform!r} "
            f"({devs[0].device_kind}), not a TPU"
        )
    if len(devs) < chips:
        raise Failed(f"the cell needs {chips} chips; JAX sees {len(devs)}")
    from midasbench import cell as cell_lib
    from midasbench import check, peaks as peaks_lib, tracecalc
    from repro.core import run_sweep, sim
    from repro.core import sweep as sweep_lib

    def compiles() -> int:
        return (
            sim._SWEEP_TRACES[0] + sweep_lib._SHARD_TRACES[0]
            + sim._RUN_TRACES[0]
        )

    cell = cell_lib.attach_program(cell_lib.build(bench, workload, seed))
    limits = bench.limits(workload)
    first = run_sweep(cell.spec)
    setup_s = time.perf_counter() - t_start
    log(f"{workload}: set-up {setup_s:.3f} s, {cell.grid_cells} grid cells")

    n_compiles = compiles()
    if trace:
        tdir = Path(cache_dir or ROOT / ".bench_cache") / "trace" / workload
        results, spans, xplane = traced_window(
            jax, run_sweep, cell.spec, int(cell.traffic["traced_sweeps"]),
            tdir,
        )
    else:
        results, t_window = timed_window(run_sweep, cell.spec, seconds)
    if compiles() != n_compiles:
        raise Failed(
            f"{compiles() - n_compiles} program(s) compiled inside the "
            f"window"
        )
    n_sweeps = len(results)
    stats = [d.memory_stats() or {} for d in devs[:chips]]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    numbers, failed = check_outputs(cell, seed, limits, first, results)
    del first, results
    correct, over = check.verdict(numbers, limits["limits"])
    if over:
        log(f"over the limit: {', '.join(over)}")

    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": peak,
    }
    out = {
        "correct": bool(correct),
        "attempted": n_sweeps * cell.grid_cells,
        "failed": int(failed),
        "metrics": {},
        "device": device,
    }
    if trace:
        tr = tracecalc.load(xplane)
        lo, hi = tracecalc.window(tr)
        notes = []
        ctx = Ctx(
            trace=tr, lo=lo, hi=hi, spans=spans, n_sweeps=n_sweeps,
            cell=cell, bench=bench,
            peaks=peaks_lib.peaks(devs[0].device_kind) if require_chip
            else {},
            devices=chips, notes=notes,
        )
        device["busy_s"] = ctx.busy_ns() / 1e9
        device["window_s"] = (hi - lo) / 1e9
        for mt in bench.metrics_for("per_layer", workload):
            v = bench.reader(mt["name"]).read(ctx)
            if v is not None:
                out["metrics"][mt["name"]] = {
                    "value": float(v), "unit": mt["unit"],
                }
        out["breakdown"] = {
            "device_ops": tracecalc.top_ops(tr, lo, hi),
            "idle_gaps": tracecalc.idle_gaps(tr, lo, hi),
        }
        for n in notes:
            log(n)
    else:
        values = {
            "cell_ticks_per_s": n_sweeps * cell.grid_cells * cell.T
            / t_window,
            "setup_s": setup_s,
            "peak_hbm_mb": peak / 1e6,
        }
        for mt in bench.metrics_for("end_to_end", workload):
            out["metrics"][mt["name"]] = {
                "value": float(values[mt["name"]]), "unit": mt["unit"],
            }
    out["checks"] = {
        k: {"value": float(numbers[k]), "limit": float(v)}
        for k, v in limits["limits"].items()
    }
    for line in check.report_lines(numbers, limits["limits"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log("FAILED: the program (src/repro) is not in this checkout")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    # the TPU runtime's logs stay inside the checkout too
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_cache" / "tpu"))
    import jax

    use_compile_cache(jax)
    from midasbench.spec import Bench

    try:
        out = run(
            Bench.from_root(ROOT), args.workload, args.seed, args.seconds,
            bool(args.trace), t_start=T_START,
        )
    except Failed as e:
        log(f"FAILED: {e}")
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
