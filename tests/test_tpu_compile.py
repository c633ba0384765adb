"""Compile the midas_route kernels, and the fleet cache's batched table
updates, for a described TPU v5e, with no chip.

The interpret-mode parity tests in ``test_kernels.py`` cannot see what
only the chip's compiler refuses (unsupported reductions, vector
gathers, tiling, VMEM).  Each test here lowers a kernel at the widths
the engine and the MoE consumer run, compiles it for one chip of a
described ``v5e:2x2`` topology, and checks that the Mosaic kernel is
in the compiled program (``tpu_custom_call``).

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.midas_route import kernel as mr_kernel


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _route_shapes(sharding, R, m, d_max, batch=()):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(batch + shape, dt, sharding=sharding)

    return (
        s((R, d_max), jnp.int32),  # feas
        s((m,), jnp.float32),  # load view
        s((m,), jnp.float32),  # p50 view
        s((R, d_max), jnp.int32),  # sampled mask
        s((R, d_max), jnp.float32),  # tie scores
        jax.ShapeDtypeStruct((1, 4), jnp.float32, sharding=sharding),
    )


@pytest.mark.parametrize("d_max", [4, 16])
@pytest.mark.parametrize("m", [8, 64])
@pytest.mark.parametrize("mode", mr_kernel.ROUTE_MODES)
def test_route_select_compiles_for_v5e(one_chip, mode, m, d_max):
    def fn(feas, load, p50, sampled, tie, scal):
        return mr_kernel.route_select(
            feas, load, p50, sampled, tie, scal, mode=mode
        )

    text = _compile_text(fn, *_route_shapes(one_chip, 512, m, d_max))
    assert "tpu_custom_call" in text


def test_route_select_compiles_for_v5e_under_vmap(one_chip):
    """The sweep runs the kernel under its seed vmap: the batching rule
    adds a grid axis, which must compile too."""
    *batched, scal = _route_shapes(one_chip, 512, 64, 4, batch=(8,))

    def fn(feas, load, p50, sampled, tie, scal):
        return jax.vmap(
            lambda f, lo, p, s, t: mr_kernel.route_select(
                f, lo, p, s, t, scal, mode="midas"
            )
        )(feas, load, p50, sampled, tie)

    assert "tpu_custom_call" in _compile_text(fn, *batched, scal)


@pytest.mark.parametrize("f_max", [1.0, 0.25])
@pytest.mark.parametrize(
    "E,k",
    [(16, 4), (128, 8)],
    ids=["dbrx_132b", "qwen3_moe_235b_a22b"],
)
def test_midas_dispatch_compiles_for_v5e(one_chip, E, k, f_max):
    logits = jax.ShapeDtypeStruct((4096, E), jnp.float32, sharding=one_chip)
    load = jax.ShapeDtypeStruct((E,), jnp.float32, sharding=one_chip)

    def fn(lg, ld):
        return mr_kernel.midas_dispatch(lg, ld, k, 2, f_max=f_max)

    assert "tpu_custom_call" in _compile_text(fn, logits, load)


def test_the_engine_names_the_routing_kernel_in_its_routing_phase(
    one_chip, monkeypatch
):
    """``route_select_us_per_tick`` reads the device ops whose name
    holds ``route_select``: the sweep program compiled for the chip
    names the kernel's custom call so, inside the ``tick/route``
    phase."""
    import dataclasses
    import re

    from repro.core import SimConfig, make_workload, sim
    from repro.kernels import common
    from repro.obs import trace as obs_trace

    # the program asks the backend it runs on; this one compiles for
    # the described chip from a CPU process
    monkeypatch.setattr(common, "interpret_mode", lambda: False)
    cfg = SimConfig(m=8, N=4096, policy="midas", middleware=("cache",),
                    route_impl="pallas")
    wl = make_workload("bursty", T=12, m=8, seed=0, N=4096)
    states = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[sim.init_state(dataclasses.replace(cfg, seed=s)) for s in (1, 2)],
    )

    def shape(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    grids = [shape(jnp.asarray(a)[None])
             for a in (wl.keys, wl.mask, wl.is_write)]
    text = sim._run_scan_sweep.lower(
        cfg, jax.tree_util.tree_map(shape, states), *grids, "summary"
    ).compile().as_text()
    calls = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text
    )
    kernel = [c for c in calls if "route_select" in c]
    assert kernel, calls
    pmap = obs_trace.parse_phases(text)
    assert {pmap.get(c) for c in kernel} == {("tick/route", "")}



def _arrays(text, opcode):
    """Per instruction of ``opcode``: (element count, rank) of every
    array in its result shape (a while's shape is its carried tuple)."""
    return [
        [(math.prod(int(d) for d in dims.split(",") if d), dims.count(",") + 1)
         for dims in re.findall(r"\b(?:pred|[fsu]\d+)\[([\d,]*)\]", shape)]
        for shape, op in re.findall(
            r"^\s*(?:ROOT )?%[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(", text, re.M)
        if op == opcode
    ]


def _grid_scan(cell):
    """A scan over ticks of ``cell`` vmapped over the grid axis, as the
    sweep engine runs a middleware stage."""

    def run(state, keys, mask, writes):
        def tick(s, x):
            s, hit = jax.vmap(cell)(s, *x)
            return s, hit.sum()

        return jax.lax.scan(tick, state, (keys, mask, writes))

    return run


@pytest.mark.parametrize("stage", ["fleet_cache", "cache"])
def test_batched_table_scatters_compile_in_place(one_chip, stage):
    """The per-key tables under the sweep's grid vmap.  A ``(G, N)``
    carry makes the TPU compiler copy the whole batch into a linear
    ``(G*N,)`` buffer and back around every scatter, in loops of
    ``dynamic-update-slice``s.  The lane-tiled tables already have that
    buffer's layout, so the scan compiles with no such loop."""
    from repro.core import cache as cache_lib
    from repro.core import fleet as fleet_lib

    G, N, R, P, T = 8, 1_000_000, 512, 128, 4
    D = fleet_lib.delay_ticks(100.0, 50.0)
    if stage == "fleet_cache":
        def cell(st, keys, mask, writes):
            proxy = fleet_lib.proxy_assign(R, P, st.tick)
            now = st.tick.astype(jnp.float32) * 50.0
            return fleet_lib.lookup_fleet(st, keys, mask, writes, proxy,
                                          now, mode="lease", gossip_ms=100.0)

        init = fleet_lib.init_fleet(N, P, D)
    else:
        def cell(st, keys, mask, writes):
            return cache_lib.lookup_batch(st, keys, mask, writes,
                                          jnp.asarray(1.0))

        init = cache_lib.init_cache(N)
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((G,) + x.shape, x.dtype,
                                       sharding=one_chip),
        init,
    )
    xs = [jax.ShapeDtypeStruct((T, G, R), dt, sharding=one_chip)
          for dt in (jnp.int32, jnp.bool_, jnp.bool_)]
    text = _compile_text(_grid_scan(cell), state, *xs)

    rows, lanes = cache_lib.table_shape(N)
    table = G * rows * lanes  # one grid-batched table, padding included
    whiles = _arrays(text, "while")
    # the scatter itself may view a tiled table as its (G*N_pad,) linear
    # buffer (a bitcast); no loop may carry such a buffer to copy into
    assert not [w for w in whiles
                if {(G * N, 1), (table, 1)} & set(w)], whiles
    if stage == "cache":
        # the scan's own loop is the only one; no slice copies a table
        assert len(whiles) == 1, whiles
        assert not [a for a in _arrays(text, "dynamic-update-slice")
                    if max(n for n, _ in a) >= G * N]
    else:
        # besides the scan, only the lag ring's reads at its batched
        # slot build loops, and each carries the (G, D, ...) ring
        assert all(D * table in {n for n, _ in w} for w in whiles), whiles
