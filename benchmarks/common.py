"""Benchmark harness helpers: timing, CSV emission, the shared runner
CLI, and the incremental JSON artifact writer.

Every sweep-style runner (E4 control, E8 scenario matrix, E10 engine,
E11 shard, E12 resilience) used to duplicate its arg parsing and its
rewrite-after-every-block JSON idiom; both now live here.  A runner
exposes ``run(opts: BenchOpts | None = None)`` (what ``benchmarks.run``
dispatches with defaults) plus a ``main()`` built from
:func:`parse_opts`, so

    PYTHONPATH=src python -m benchmarks.control_stability --only aimd
    PYTHONPATH=src python -m benchmarks.scenario_matrix --seeds 2 \
        --devices 4 --out /tmp/artifacts

work uniformly: ``--only`` filters the runner's primary sweep axis
(controllers / policies / faults / configs), ``--seeds`` overrides the
seed count per cell, ``--devices`` shards each sweep's seed axis over an
emulated or real device mesh (``SweepSpec.devices``), and ``--out``
redirects the JSON artifacts.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from repro.obs import trace as obs_trace

ROWS: List[Tuple[str, float, str]] = []

ROOT = Path(__file__).resolve().parents[1]
# default artifact directory — every sweep runner writes here
OUT = ROOT / "experiments" / "sim"
# JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is
# unset: a fixed path, since the path is part of every cache key
CACHE_DIR = ROOT / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory.

    Called by entry points only (``chip_smoke.py``, ``benchmarks.run``
    and every runner's :func:`parse_opts`), never on library import or
    in tests.  ``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise it
    is set to :data:`CACHE_DIR`, so worker processes inherit it.  Sets
    JAX's config only where JAX is already imported: a parent that
    launches chip workers must not import it on their behalf.
    """
    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CACHE_DIR))
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path


def emit(name: str, us_per_call: float, derived: str) -> None:
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def timed(fn: Callable, *args, repeat: int = 1, label: str = "", **kw):
    """Run fn, return (result, us_per_call) — first call includes compile,
    so time the SECOND call when repeat > 1.  Both halves are recorded
    as flight-recorder spans (``bench/first_call`` / ``bench/steady``)
    so ``repro-report`` can split compile from execute time."""
    with obs_trace.span("bench/first_call", cat="bench", label=label):
        out = fn(*args, **kw)
    with obs_trace.span(
        "bench/steady", cat="bench", label=label, repeat=repeat
    ):
        t0 = time.perf_counter()
        for _ in range(repeat):
            out = fn(*args, **kw)
        dt = (time.perf_counter() - t0) / repeat
    return out, dt * 1e6


@dataclasses.dataclass(frozen=True)
class BenchOpts:
    """Parsed shared CLI options, with runner defaults as fallbacks."""

    only: Tuple[str, ...] = ()
    n_seeds: Optional[int] = None
    devices: int = 1
    out: Optional[Path] = None

    def pick(self, values: Sequence[str], axis: str) -> Tuple[str, ...]:
        """Filter a runner's primary sweep axis by ``--only`` (no-op
        when unset); unknown names raise with the alternatives."""
        values = tuple(values)
        if not self.only:
            return values
        unknown = [o for o in self.only if o not in values]
        if unknown:
            raise ValueError(
                f"unknown {axis} {', '.join(map(repr, unknown))}; "
                f"available: {', '.join(values)}"
            )
        return tuple(v for v in values if v in self.only)

    def seeds(self, default: Tuple[int, ...]) -> Tuple[int, ...]:
        """Seed tuple: ``--seeds N`` means seeds 0..N-1."""
        if self.n_seeds is None:
            return tuple(default)
        return tuple(range(self.n_seeds))


def parse_opts(
    argv: Optional[Sequence[str]] = None,
    *,
    prog: str,
    description: str,
    axis: str = "cells",
) -> BenchOpts:
    """The shared runner CLI (``--only``, ``--seeds``, ``--devices``,
    ``--out``); also places the compile cache (:func:`use_compile_cache`)."""
    use_compile_cache()
    ap = argparse.ArgumentParser(prog=prog, description=description)
    ap.add_argument(
        "--only",
        default="",
        help=f"comma-separated subset of this runner's {axis}",
    )
    ap.add_argument(
        "--seeds",
        type=int,
        default=None,
        metavar="N",
        help="run seeds 0..N-1 per cell (overrides the runner default)",
    )
    ap.add_argument(
        "--devices",
        type=int,
        default=1,
        help="shard each sweep's seed axis over this many devices "
        "(SweepSpec.devices; on CPU needs XLA_FLAGS="
        "--xla_force_host_platform_device_count)",
    )
    ap.add_argument(
        "--out",
        default=None,
        metavar="DIR",
        help="artifact output directory (default: experiments/sim)",
    )
    args = ap.parse_args(argv)
    only = tuple(s.strip() for s in args.only.split(",") if s.strip())
    return BenchOpts(
        only=only,
        n_seeds=args.seeds,
        devices=args.devices,
        out=Path(args.out) if args.out else None,
    )


def rows_equal(ra, rb) -> bool:
    """Two sweep rows (``SimResult`` or ``SummaryResult``) bitwise equal
    on every result field; ``config`` and ``final_cache`` are skipped."""
    names = (
        ra._fields
        if hasattr(ra, "_fields")
        else tuple(f.name for f in dataclasses.fields(ra))
    )
    for name in names:
        if name in ("config", "final_cache"):
            continue
        a, b = getattr(ra, name), getattr(rb, name)
        if a is None or b is None:
            if a is not b:
                return False
            continue
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            return False
    return True


def _env_meta() -> dict:
    """Environment provenance every artifact records: numbers without
    the stack + device that produced them aren't comparable.

    A process that has not imported JAX records nothing here: it is a
    launcher whose workers need the device, and asking for the devices
    would make it hold them (it records what its workers report).
    """
    jax = sys.modules.get("jax")
    if jax is None:
        return {}
    devs = jax.devices()
    return {
        "jax_version": jax.__version__,
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "n_devices": len(devs),
    }


def _utc(ts: float) -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(ts))


class Artifact:
    """Incremental JSON artifact: call :meth:`write` after every block,
    rewriting the whole doc — a CI timeout (rc 124, tolerated) still
    uploads valid partial JSON.

    Every artifact is paired with a flight-recorder trace: constructing
    one points the process-global recorder at ``<stem>.trace.jsonl``
    (write-through JSONL) and each :meth:`write` refreshes the
    Chrome-trace export ``<stem>.trace.json`` plus the artifact's
    ``meta`` block (jax version, device kind, wall-clock start/end).
    """

    def __init__(self, filename: str, out: Optional[Path] = None):
        base = out if out is not None else OUT
        base.mkdir(parents=True, exist_ok=True)
        self.path = base / filename
        self.started = time.time()
        self.trace_path = self.path.with_suffix(".trace.jsonl")
        obs_trace.configure(path=self.trace_path, fresh=True)

    def write(self, doc: dict) -> None:
        meta = doc.setdefault("meta", {})
        meta.update(_env_meta())
        meta.setdefault("started_at", _utc(self.started))
        meta["written_at"] = _utc(time.time())
        meta["trace_file"] = self.trace_path.name
        self.path.write_text(json.dumps(doc, indent=1))
        if obs_trace.RECORDER.enabled:
            obs_trace.RECORDER.write_chrome(
                self.path.with_suffix(".trace.json")
            )
