"""Bring-up smoke test: the MIDAS sweep engine and its routing kernel on
one TPU chip, at paper scale, through the entry points a user calls.

    python chip_smoke.py               # one chip: device, testbed, fleet,
                                       # kernel phases
    python chip_smoke.py --four-chips  # the sharded fleet sweep only

Phases, all in this one process (a chip belongs to one process):

* ``device``  — refuses to run unless JAX's first device is a TPU, and
  unless ``route_impl="auto"`` resolves to the compiled Pallas kernel.
* ``testbed`` — E8's paper testbed (m=8): midas + cache on ``bursty``
  and ``rename_storm``, power_of_d and chbl on ``bursty`` (so every
  ``route_select`` mode runs), T=1200, 8 seeds, ``run_sweep`` with
  ``route_impl="auto"`` and again with ``"ref"``; the rows must be
  bitwise equal (DESIGN.md §15).  Each sweep program is also lowered
  and compiled on its own, and must hold the Mosaic kernel
  (``tpu_custom_call``).
* ``fleet``   — E11's million-key fleet (m=64, V=64, P=128, N=10⁶,
  R=512, T=240, 4 scenarios x 8 seeds), auto against ref as above, and
  every summary finite.
* ``kernel``  — ``ops.midas_dispatch`` at T=4096 at the expert widths of
  dbrx_132b and qwen3_moe_235b_a22b, f_max 1.0 and 0.25, against
  ``ref.midas_dispatch`` on the chip.
* ``--four-chips`` — the fleet sweep with ``SweepSpec(devices=4)``
  against ``devices=1``, and a padded 7-seed grid: bitwise equal rows.

Workloads and inputs come from seeds; nothing is read from outside the
checkout.  Each run prints one ``phase {...}`` line (compile, first-call
and steady seconds, peak device bytes, device kind: informational) and,
last, ``{"ok": true, "device": {...}}``.  Any failed check exits
non-zero without that line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

SEEDS = tuple(range(8))
# (policy, workloads) of the testbed phase: the E8 midas stack on the
# read-hot and the write-hot scenario, plus one sweep per other mode
TESTBED = (
    ("midas", ("bursty", "rename_storm")),
    ("power_of_d", ("bursty",)),
    ("chbl", ("bursty",)),
)
# the MoE configs whose expert widths the dispatch consumer runs at
DISPATCH = ("dbrx_132b", "qwen3_moe_235b_a22b")
DISPATCH_T = 4096
F_MAX = (1.0, 0.25)
FOUR = 4


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def report(**fields) -> None:
    print("phase " + json.dumps(fields), flush=True)


def peak_bytes(jax) -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def check_device(jax, n_chips: int):
    """The device phase: a TPU, the kernel path, no override."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(
            f"no TPU found: JAX's first device is {devs[0].platform!r} "
            f"({devs[0].device_kind})"
        )
    if len(devs) < n_chips:
        fail(f"{n_chips} chips needed, JAX sees {len(devs)}")
    from repro.kernels import common

    if common.resolve_route_impl("auto") != "pallas":
        fail("route_impl='auto' does not resolve to the Pallas kernel")
    if common.interpret_mode():
        fail("Pallas would run in interpret mode on this backend")
    return devs


def require_kernel(compiled, what: str) -> None:
    if "tpu_custom_call" not in compiled.as_text():
        fail(f"{what}: the compiled program holds no Mosaic kernel")


def compile_sweep(spec):
    """Lower and compile the program ``run_sweep`` runs for ``spec``
    (one policy, one device), built from the same arguments."""
    import jax
    import jax.numpy as jnp

    from repro.core import sim

    cfg = dataclasses.replace(
        spec.config, policy=spec.policies[0], controller=spec.controllers[0]
    )
    # target values are data, not shapes: any pair gives the same program
    states = [
        sim.init_state(dataclasses.replace(cfg, seed=s), 0.5, 400.0)
        for s in spec.seeds
    ]
    states = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)
    grids = [
        jnp.stack([getattr(w, f) for w in spec.workloads])
        for f in ("keys", "mask", "is_write")
    ]
    return sim._run_scan_sweep.lower(cfg, states, *grids, spec.metrics).compile()


def timed_sweep(spec):
    """``run_sweep`` twice: first call (compile or cache load included)
    and steady call; ``run_sweep`` returns host rows, so both end with
    the device done.  The two results must agree."""
    from benchmarks.common import rows_equal
    from repro.core import run_sweep

    t0 = time.perf_counter()
    first = run_sweep(spec)
    t1 = time.perf_counter()
    res = run_sweep(spec)
    t2 = time.perf_counter()
    if not all(rows_equal(first.cells[c], res.cells[c]) for c in res.cells):
        fail(f"two identical sweeps disagree: {spec.policies}")
    return res, t1 - t0, t2 - t1


def check_rows_equal(name: str, a, b) -> None:
    from benchmarks.common import rows_equal

    if set(a.cells) != set(b.cells):
        fail(f"{name}: the two sweeps cover different grids")
    bad = [c for c in a.cells if not rows_equal(a.cells[c], b.cells[c])]
    if bad:
        fail(f"{name}: {len(bad)}/{len(a.cells)} rows differ, e.g. {bad[0]}")


def check_finite(name: str, res) -> None:
    import numpy as np

    for coord, row in res.items():
        vals = [
            row.mean_queue(),
            row.max_queue(),
            row.worst_case_queue(),
            row.dispersion(),
            *row.latency_quantiles(),
        ]
        if not np.all(np.isfinite(vals)):
            fail(f"{name}: non-finite summary at {coord}: {vals}")


def auto_vs_ref(jax, phase: str, name: str, spec) -> None:
    """One sweep on the kernel path against the same sweep on ``ref``."""
    t0 = time.perf_counter()
    compiled = compile_sweep(spec)
    compile_s = time.perf_counter() - t0
    require_kernel(compiled, f"{phase}/{name}")
    rows = {}
    for impl in ("auto", "ref"):
        spec_i = dataclasses.replace(
            spec, config=dataclasses.replace(spec.config, route_impl=impl)
        )
        rows[impl], first_s, steady_s = timed_sweep(spec_i)
        report(
            phase=phase,
            sweep=name,
            route_impl=impl,
            cells=spec.n_cells,
            compile_s=compile_s if impl == "auto" else None,
            first_s=first_s,
            steady_s=steady_s,
            peak_bytes_in_use=peak_bytes(jax),
            device_kind=jax.devices()[0].device_kind,
        )
    check_rows_equal(f"{phase}/{name} auto vs ref", rows["auto"], rows["ref"])
    check_finite(f"{phase}/{name}", rows["auto"])


def phase_testbed(jax) -> None:
    from benchmarks import scenario_matrix as e8
    from repro.core import SimConfig, SweepSpec, make_workload

    wls = {
        n: make_workload(n, T=e8.T, m=e8.M, seed=e8.SEED)
        for n in ("bursty", "rename_storm")
    }
    for policy, names in TESTBED:
        spec = SweepSpec(
            config=SimConfig(
                m=e8.M, middleware=e8.POLICY_STACKS.get(policy, ())
            ),
            workloads=tuple(wls[n] for n in names),
            policies=(policy,),
            seeds=SEEDS,
            metrics="summary",
        )
        auto_vs_ref(jax, "testbed", policy, spec)


def phase_fleet(jax) -> None:
    from benchmarks import shard_sweep as e11

    auto_vs_ref(jax, "fleet", "midas", e11.fleet_spec(seeds=SEEDS))


def phase_kernel(jax) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.kernels.midas_route import ops, ref

    for cfg_name in DISPATCH:
        moe = getattr(configs, cfg_name).FULL.moe
        E, k, d = moe.num_experts, moe.experts_per_token, moe.midas_d
        keys = jax.random.split(jax.random.PRNGKey(E), 2)
        logits = jax.random.normal(keys[0], (DISPATCH_T, E)) * 2.0
        # loads skewed enough that the f_max=0.25 cap binds in both
        # configs, so the two-pass kernel's quantile decides some tokens
        load = jnp.abs(jax.random.normal(keys[1], (E,))) * 10.0
        uncapped = None
        for f_max in F_MAX:

            def fn(lg, ld, f_max=f_max):
                return ops.midas_dispatch(lg, ld, k, d, f_max=f_max)

            t0 = time.perf_counter()
            compiled = jax.jit(fn).lower(logits, load).compile()
            t1 = time.perf_counter()
            name = f"kernel/{cfg_name}/f_max={f_max}"
            require_kernel(compiled, name)
            jax.block_until_ready(compiled(logits, load))
            t2 = time.perf_counter()
            e_k, w_k, s_k = jax.block_until_ready(compiled(logits, load))
            t3 = time.perf_counter()
            e_r, w_r, s_r = ref.midas_dispatch(
                logits, load, k, d, f_max=f_max
            )
            if not np.array_equal(np.asarray(e_k), np.asarray(e_r)):
                fail(f"{name}: expert ids differ from ref")
            if not np.array_equal(np.asarray(s_k), np.asarray(s_r)):
                fail(f"{name}: steered flags differ from ref")
            if not np.allclose(
                np.asarray(w_k), np.asarray(w_r), rtol=1e-5, atol=1e-5
            ):
                fail(f"{name}: weights differ from ref")
            steered = int(np.asarray(s_k).sum())
            if f_max >= 1.0:  # F_MAX runs the uncapped variant first
                uncapped = steered
            elif steered >= uncapped:
                fail(f"{name}: the f_max cap steered no fewer tokens")
            report(
                phase="kernel",
                config=cfg_name,
                T=DISPATCH_T,
                E=E,
                k=k,
                d=d,
                f_max=f_max,
                steered=steered,
                compile_s=t1 - t0,
                first_s=t2 - t1,
                steady_s=t3 - t2,
                peak_bytes_in_use=peak_bytes(jax),
                device_kind=jax.devices()[0].device_kind,
            )


def phase_four_chips(jax, devs) -> None:
    """Sharded fleet sweep on four chips against one chip, bitwise,
    for a dividing (8) and a padded (7) seed count."""
    chips = devs[:FOUR]
    coords = [tuple(d.coords) for d in chips]
    if len(set(coords)) != FOUR:
        fail(f"jax.devices()[:{FOUR}] does not span {FOUR} chips: {coords}")
    from benchmarks import shard_sweep as e11

    for seeds in (SEEDS, SEEDS[:-1]):
        rows = {}
        for n_dev in (FOUR, 1):
            rows[n_dev], first_s, steady_s = timed_sweep(
                e11.fleet_spec(seeds=seeds, devices=n_dev)
            )
            report(
                phase="four_chips",
                devices=n_dev,
                seeds=len(seeds),
                padded=bool(len(seeds) % FOUR),
                cells=len(rows[n_dev].cells),
                first_s=first_s,
                steady_s=steady_s,
                peak_bytes_in_use=peak_bytes(jax),
                device_kind=chips[0].device_kind,
                chip_coords=coords,
            )
        check_rows_equal(
            f"four_chips/{len(seeds)} seeds: devices={FOUR} vs 1",
            rows[FOUR],
            rows[1],
        )
        check_finite(f"four_chips/{len(seeds)} seeds", rows[FOUR])


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--four-chips",
        action="store_true",
        help="run only the sharded fleet sweep, on four chips",
    )
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_KERNEL_IMPL"):
        fail("REPRO_KERNEL_IMPL is set; it would override the kernel choice")
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no repro package under {ROOT / 'src'}: run from a checkout")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.common import use_compile_cache

    use_compile_cache()
    import jax

    devs = check_device(jax, FOUR if args.four_chips else 1)
    if args.four_chips:
        phase_four_chips(jax, devs)
    else:
        phase_testbed(jax)
        phase_fleet(jax)
        phase_kernel(jax)
    d0 = devs[0]
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": d0.platform,
                    "kind": d0.device_kind,
                    "count": len(devs),
                },
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
