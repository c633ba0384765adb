"""Compile the midas_route kernels for a described TPU v5e, with no chip.

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

import os

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
