"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (stdout) and writes artifacts
under experiments/.  E-numbers refer to DESIGN.md §6.

  PYTHONPATH=src python -m benchmarks.run [--only paper,theory,...] [--list]
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import use_compile_cache

SECTIONS = {
    "paper": "benchmarks.paper_claims",        # E1+E2 (Fig 3/4 + §VI table)
    "theory": "benchmarks.theory",             # E3
    "control": "benchmarks.control_stability",  # E4
    "cache": "benchmarks.cache",               # E5
    "moe": "benchmarks.moe_balance",           # E6
    "ckpt": "benchmarks.ckpt_storm",           # E7
    "scenario_matrix": "benchmarks.scenario_matrix",  # E8
    "fleet": "benchmarks.fleet",               # E9 (gossip × coherence)
    "engine": "benchmarks.engine_perf",        # E10 (compile + ticks/sec)
    "shard": "benchmarks.shard_sweep",         # E11 (sharded 10^6-key sweep)
    "resilience": "benchmarks.resilience",     # E12 (fault x policy x ctrl)
    "redteam": "benchmarks.redteam",           # E13 (adversarial x ±guard)
    "serving": "benchmarks.serving",
    "kernels": "benchmarks.kernels_bench",
    "ablations": "benchmarks.ablations",       # §IV-E stability guards
}


def main() -> None:
    ap = argparse.ArgumentParser(
        description="MIDAS benchmark suite (see DESIGN.md §6)")
    ap.add_argument("--only", default=None,
                    help="comma-separated section names")
    ap.add_argument("--list", action="store_true",
                    help="list available sections and exit")
    args = ap.parse_args()
    if args.list:
        for name, mod in SECTIONS.items():
            print(f"{name:10s} {mod}")
        return
    if args.only:
        # tolerate whitespace and stray commas; run each section once, in
        # the order first named
        names = []
        for n in (s.strip() for s in args.only.split(",")):
            if n and n not in names:
                names.append(n)
        if not names:
            ap.error("--only named no sections; "
                     f"available: {', '.join(SECTIONS)} (try --list)")
    else:
        names = list(SECTIONS)
    unknown = [n for n in names if n not in SECTIONS]
    if unknown:
        ap.error(f"unknown section(s): {', '.join(unknown)}; "
                 f"available: {', '.join(SECTIONS)} (try --list)")
    use_compile_cache()
    print("name,us_per_call,derived")
    t0 = time.time()
    for name in names:
        mod = __import__(SECTIONS[name], fromlist=["run"])
        try:
            mod.run()
        except Exception as e:   # pragma: no cover
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            raise
    print(f"# total {time.time() - t0:.0f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
