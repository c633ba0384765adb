"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp oracle,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import kernel as fa_kernel
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.decode_attention import kernel as da_kernel
from repro.kernels.decode_attention import ref as da_ref
from repro.kernels.ssm_scan import kernel as ssm_kernel
from repro.kernels.ssm_scan import ops as ssm_ops
from repro.kernels.ssm_scan import ref as ssm_ref
from repro.kernels.midas_route import kernel as mr_kernel
from repro.kernels.midas_route import ref as mr_ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FA_CASES = [
    # (B, S, H, KV, D, window, softcap, dtype)
    (1, 128, 4, 2, 64, 0, 0.0, jnp.float32),
    (2, 256, 8, 8, 64, 0, 0.0, jnp.float32),
    (1, 256, 4, 1, 128, 0, 0.0, jnp.bfloat16),
    (1, 256, 8, 2, 64, 64, 0.0, jnp.float32),     # sliding window
    (1, 128, 4, 4, 64, 0, 50.0, jnp.float32),     # softcap (gemma2)
    (1, 256, 2, 2, 256, 128, 30.0, jnp.bfloat16),  # window + softcap
]


@pytest.mark.parametrize("B,S,H,KV,D,window,softcap,dtype", FA_CASES)
def test_flash_attention_matches_ref(B, S, H, KV, D, window, softcap, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (B, S, H, D), dtype)
    k = jax.random.normal(k2, (B, S, KV, D), dtype)
    v = jax.random.normal(k3, (B, S, KV, D), dtype)
    want = fa_ref.mha(q, k, v, causal=True, window=window, softcap=softcap)
    got = fa_kernel.flash_attention(q, k, v, causal=True, window=window,
                                    softcap=softcap, block_q=64, block_k=64,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_attention_block_size_invariance():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (1, 256, 4, 64))
    k = jax.random.normal(k2, (1, 256, 2, 64))
    v = jax.random.normal(k3, (1, 256, 2, 64))
    outs = [fa_kernel.flash_attention(q, k, v, block_q=bq, block_k=bk,
                                      interpret=True)
            for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

DA_CASES = [
    # (B, S, H, KV, D, window, softcap, dtype)
    (2, 256, 8, 2, 64, 0, 0.0, jnp.float32),
    (1, 512, 4, 4, 64, 0, 0.0, jnp.bfloat16),
    (2, 256, 8, 8, 128, 0, 0.0, jnp.float32),
    (2, 256, 4, 2, 64, 128, 0.0, jnp.float32),
    (1, 256, 8, 4, 64, 0, 50.0, jnp.float32),
]


@pytest.mark.parametrize("B,S,H,KV,D,window,softcap,dtype", DA_CASES)
def test_decode_attention_matches_ref(B, S, H, KV, D, window, softcap,
                                      dtype):
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(keys[0], (B, H, D), dtype)
    kc = jax.random.normal(keys[1], (B, S, KV, D), dtype)
    vc = jax.random.normal(keys[2], (B, S, KV, D), dtype)
    pos = jax.random.randint(keys[3], (B,), 1, S - 1)
    want = da_ref.decode_attention(q, kc, vc, pos, window=window,
                                   softcap=softcap)
    got = da_kernel.decode_attention(q, kc, vc, pos, window=window,
                                     softcap=softcap, block_k=64,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# selective scan
# ---------------------------------------------------------------------------

SSM_CASES = [
    # (Bt, S, DI, ST, chunk, dtype)
    (2, 64, 32, 8, 16, jnp.float32),
    (1, 128, 64, 16, 32, jnp.float32),
    (2, 96, 32, 8, 32, jnp.bfloat16),     # S not a chunk multiple
]


@pytest.mark.parametrize("Bt,S,DI,ST,chunk,dtype", SSM_CASES)
def test_chunked_scan_matches_sequential_ref(Bt, S, DI, ST, chunk, dtype):
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(keys[0], (Bt, S, DI), dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (Bt, S, DI), dtype))
    A = -jnp.exp(jax.random.normal(keys[2], (DI, ST)) * 0.5)
    B = jax.random.normal(keys[3], (Bt, S, ST), dtype)
    C = jax.random.normal(keys[4], (Bt, S, ST), dtype)
    D = jnp.ones((DI,))
    y_ref, h_ref = ssm_ref.selective_scan(x, dt, A, B, C, D)
    y_fast, h_fast = ssm_ops.selective_scan(x, dt, A, B, C, D, chunk=chunk,
                                            impl="jnp_chunked")
    np.testing.assert_allclose(np.asarray(y_fast, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=3e-2 if dtype == jnp.bfloat16 else 1e-4)
    np.testing.assert_allclose(np.asarray(h_fast), np.asarray(h_ref),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("Bt,S,DI,ST,chunk", [(2, 64, 32, 8, 16),
                                              (1, 96, 16, 4, 32)])
def test_parallel_scan_matches_sequential_ref(Bt, S, DI, ST, chunk):
    keys = jax.random.split(jax.random.PRNGKey(9), 5)
    x = jax.random.normal(keys[0], (Bt, S, DI))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (Bt, S, DI)))
    A = -jnp.exp(jax.random.normal(keys[2], (DI, ST)) * 0.5)
    B = jax.random.normal(keys[3], (Bt, S, ST))
    C = jax.random.normal(keys[4], (Bt, S, ST))
    D = jnp.ones((DI,))
    y_ref, h_ref = ssm_ref.selective_scan(x, dt, A, B, C, D)
    y_p, h_p = ssm_ops.selective_scan(x, dt, A, B, C, D, chunk=chunk,
                                      impl="parallel")
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_p), np.asarray(h_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("Bt,Q,DI,ST,tile", [
    (2, 16, 32, 8, 16),
    (1, 32, 64, 16, 32),
    (2, 16, 32, 8, 32),
])
def test_pallas_chunk_scan_matches_ref(Bt, Q, DI, ST, tile):
    keys = jax.random.split(jax.random.PRNGKey(4), 6)
    x = jax.random.normal(keys[0], (Bt, Q, DI))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (Bt, Q, DI)))
    A = -jnp.exp(jax.random.normal(keys[2], (DI, ST)) * 0.5)
    B = jax.random.normal(keys[3], (Bt, Q, ST))
    C = jax.random.normal(keys[4], (Bt, Q, ST))
    h0 = jax.random.normal(keys[5], (Bt, DI, ST))
    # oracle: sequential scan from h0, minus the D*x skip (kernel contract)
    y_ref, h_ref = ssm_ref.selective_scan(x, dt, A, B, C,
                                          jnp.zeros((DI,)), h0=h0)
    y_k, h_k = ssm_kernel.chunk_scan(h0, x, dt, A, B, C, tile=tile,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_ref),
                               rtol=1e-4, atol=1e-4)


def test_ssm_decode_step_matches_scan():
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    Bt, S, DI, ST = 2, 8, 16, 4
    x = jax.random.normal(keys[0], (Bt, S, DI))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (Bt, S, DI)))
    A = -jnp.exp(jax.random.normal(keys[2], (DI, ST)) * 0.5)
    B = jax.random.normal(keys[3], (Bt, S, ST))
    C = jax.random.normal(keys[4], (Bt, S, ST))
    D = jnp.ones((DI,))
    y_ref, h_ref = ssm_ref.selective_scan(x, dt, A, B, C, D)
    h = jnp.zeros((Bt, DI, ST))
    ys = []
    for t in range(S):
        y, h = ssm_ref.selective_step(x[:, t], dt[:, t], A, B[:, t],
                                      C[:, t], D, h)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(y_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# midas route
# ---------------------------------------------------------------------------

MR_CASES = [
    # (T, E, k, d)
    (256, 8, 2, 2),
    (256, 16, 4, 2),
    (512, 128, 8, 4),
    (256, 4, 2, 2),
]


@pytest.mark.parametrize("T,E,k,d", MR_CASES)
def test_midas_route_kernel_matches_ref(T, E, k, d):
    keys = jax.random.split(jax.random.PRNGKey(6), 2)
    logits = jax.random.normal(keys[0], (T, E)) * 2.0
    load = jnp.abs(jax.random.normal(keys[1], (E,))) * 3.0
    # f_max=1.0: margin-governed variant on both paths
    e_ref, w_ref, s_ref = mr_ref.midas_dispatch(
        logits, load, k, d, delta_l=2.0, gate_slack=1.0, f_max=1.0)
    e_k, w_k, s_k = mr_kernel.midas_dispatch(
        logits, load, k, d, delta_l=2.0, gate_slack=1.0, tile=128,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(e_k), np.asarray(e_ref))
    np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_ref))


def test_midas_route_reduces_load_dispersion():
    """Steering must push the realized expert load toward balance when
    telemetry is imbalanced — the paper's claim at the MoE layer."""
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    T, E, k = 4096, 16, 4
    logits = jax.random.normal(keys[0], (T, E)) * 2.0
    # pretend experts 0..3 are hot
    load = jnp.asarray([5.0] * 4 + [0.5] * 12)
    e_van, _ = mr_ref.topk_dispatch(logits, k)
    e_mid, _, steered = mr_ref.midas_dispatch(logits, load, k, d=4,
                                              delta_l=2.0, f_max=1.0)
    def hot_share(e):
        return float((np.asarray(e) < 4).mean())
    assert steered.sum() > 0
    assert hot_share(e_mid) < hot_share(e_van)


def test_midas_route_respects_fmax_zero():
    keys = jax.random.split(jax.random.PRNGKey(8), 2)
    logits = jax.random.normal(keys[0], (256, 8))
    load = jnp.abs(jax.random.normal(keys[1], (8,))) * 5.0
    e0, _, s0 = mr_ref.midas_dispatch(logits, load, 2, 2, f_max=0.0)
    e_van, _ = mr_ref.topk_dispatch(logits, 2)
    assert not bool(s0.any())
    np.testing.assert_array_equal(np.asarray(e0), np.asarray(e_van))


def _mr_inputs(T, E, seed=6):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    logits = jax.random.normal(keys[0], (T, E)) * 2.0
    load = jnp.abs(jax.random.normal(keys[1], (E,))) * 3.0
    return logits, load


MR_FMAX_CASES = [
    # (T, E, k, d, f_max, tile) — capped variant + edge tiles/padding
    (256, 16, 4, 2, 0.5, 8),        # tiny tile
    (256, 16, 4, 2, 0.5, 256),      # one-tile grid
    (250, 16, 4, 2, 0.25, 128),     # T % tile != 0 (padding path)
    (37, 8, 2, 2, 0.5, 8),          # padding + tiny tile
    (512, 128, 8, 4, 0.25, 256),
    (250, 16, 4, 2, 1.0, 128),      # padding on the margin-only kernel
]


@pytest.mark.parametrize("T,E,k,d,f_max,tile", MR_FMAX_CASES)
def test_midas_route_fmax_capped_matches_ref(T, E, k, d, f_max, tile):
    """The f_max-capped two-pass kernel (and the ragged-T padding) must
    be bit-for-bit against the pure-jnp reference."""
    logits, load = _mr_inputs(T, E)
    e_ref, w_ref, s_ref = mr_ref.midas_dispatch(
        logits, load, k, d, delta_l=2.0, gate_slack=1.0, f_max=f_max)
    e_k, w_k, s_k = mr_kernel.midas_dispatch(
        logits, load, k, d, delta_l=2.0, gate_slack=1.0, f_max=f_max,
        tile=tile, interpret=True)
    np.testing.assert_array_equal(np.asarray(e_k), np.asarray(e_ref))
    np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_ref))


@pytest.mark.parametrize("case", ["tied_alt_loads", "tied_logits"])
def test_midas_route_single_pass_ties_match_ref(case):
    """Single-pass (f_max=1) kernel vs ref where its selections tie:
    alternates with equal loads (the steer target is the lowest slot)
    and equal gate logits (top-(k+d) keeps the lowest expert id)."""
    T, E, k, d = 256, 16, 4, 4
    logits, _ = _mr_inputs(T, E)
    load = jnp.asarray([6.0, 0.0, 3.0, 0.0] * 4)
    if case == "tied_logits":
        # + 0.0 folds -0.0 into 0.0: top_k orders the two, == does not
        logits = jnp.round(logits) + 0.0
    e_ref, w_ref, s_ref = mr_ref.midas_dispatch(logits, load, k, d)
    e_k, w_k, s_k = mr_kernel.midas_dispatch(
        logits, load, k, d, tile=128, interpret=True)
    assert bool(np.asarray(s_ref).any())
    np.testing.assert_array_equal(np.asarray(e_k), np.asarray(e_ref))
    np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_ref))


def test_midas_route_kernel_deff_zero_falls_back():
    """d_eff <= 0 (k + d spans all experts) collapses to plain top-k on
    every path — kernel, ref, and the ops wrapper agree."""
    logits, load = _mr_inputs(128, 4)
    e_van, w_van = mr_ref.topk_dispatch(logits, 4)
    for fn in (mr_kernel.midas_dispatch, mr_ref.midas_dispatch):
        e, w, s = fn(logits, load, 4, 2)
        np.testing.assert_array_equal(np.asarray(e), np.asarray(e_van))
        np.testing.assert_allclose(np.asarray(w), np.asarray(w_van),
                                   rtol=1e-6, atol=1e-6)
        assert not bool(np.asarray(s).any())


def test_midas_route_ops_env_forces_both_directions(monkeypatch):
    """REPRO_KERNEL_IMPL must force the ops wrapper onto either path —
    including pallas with f_max < 1, which used to silently decline."""
    from repro.kernels.midas_route import kernel as kernel_mod
    from repro.kernels.midas_route import ops as mr_ops

    logits, load = _mr_inputs(64, 8)
    calls = []
    real = kernel_mod.midas_dispatch

    def spy(*a, **kw):
        calls.append(kw)
        return real(*a, **kw)

    monkeypatch.setattr(kernel_mod, "midas_dispatch", spy)
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    e_p, w_p, s_p = mr_ops.midas_dispatch(logits, load, 2, 2, f_max=0.5)
    assert len(calls) == 1  # pallas path taken despite f_max < 1

    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
    e_r, w_r, s_r = mr_ops.midas_dispatch(logits, load, 2, 2, f_max=0.5)
    assert len(calls) == 1  # ref forced: kernel not touched again
    np.testing.assert_array_equal(np.asarray(e_p), np.asarray(e_r))
    np.testing.assert_array_equal(np.asarray(s_p), np.asarray(s_r))
    np.testing.assert_allclose(np.asarray(w_p), np.asarray(w_r),
                               rtol=1e-6, atol=1e-6)


def test_midas_route_ops_warns_once_when_pallas_declined(monkeypatch):
    """impl='pallas' with no kernel work (d_eff <= 0) is surfaced by a
    one-time RuntimeWarning, not silently rerouted."""
    import warnings as warnings_mod

    from repro.kernels.midas_route import ops as mr_ops

    logits, load = _mr_inputs(64, 4)
    monkeypatch.setattr(mr_ops, "_DECLINED_WARNED", False)
    with warnings_mod.catch_warnings(record=True) as w:
        warnings_mod.simplefilter("always")
        mr_ops.midas_dispatch(logits, load, 4, 2, impl="pallas")
        mr_ops.midas_dispatch(logits, load, 4, 2, impl="pallas")
    declined = [x for x in w if "declined" in str(x.message)]
    assert len(declined) == 1


# ---------------------------------------------------------------------------
# route_select: the engine's wave-routing kernel vs the jnp policies
# ---------------------------------------------------------------------------

RS_CASES = [
    # (R, m, d_max, tile) — includes R % tile != 0 (padding)
    (256, 8, 4, 128),
    (100, 8, 4, 128),
    (64, 32, 8, 8),
    (7, 4, 2, 256),
]


def _rs_inputs(R, m, d_max, seed=11):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    feas = jax.random.randint(keys[0], (R, d_max), 0, m, jnp.int32)
    load = jnp.abs(jax.random.normal(keys[1], (m,))) * 3.0
    p50 = jnp.abs(jax.random.normal(keys[2], (m,))) * 50.0
    rng = keys[3]
    return feas, load, p50, rng


@pytest.mark.parametrize("R,m,d_max,tile", RS_CASES)
def test_route_select_power_of_d_matches_jnp(R, m, d_max, tile):
    from repro.core.policies.base import sample_candidates

    feas, load, _, rng = _rs_inputs(R, m, d_max)
    sampled = sample_candidates(rng, feas, 2)
    tie = jax.random.uniform(jax.random.fold_in(rng, 1), feas.shape) * 1e-3
    loadv = jnp.where(sampled, load[feas], jnp.inf)
    best = jnp.argmin(loadv + tie, axis=1)
    want = jnp.take_along_axis(feas, best[:, None], axis=1)[:, 0]
    got, _ = mr_kernel.route_select(
        feas, load, load, sampled.astype(jnp.int32), tie,
        jnp.zeros((1, 4), jnp.float32), mode="power_of_d", tile=tile,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("R,m,d_max,tile", RS_CASES)
def test_route_select_midas_matches_jnp(R, m, d_max, tile):
    from repro.core.policies.base import sample_candidates

    feas, load, p50, rng = _rs_inputs(R, m, d_max)
    delta_l, delta_t = 0.5, 10.0
    sampled = sample_candidates(rng, feas, 3).at[:, 0].set(False)
    tie = jax.random.uniform(jax.random.fold_in(rng, 2), feas.shape) * 1e-3
    Lp = load[feas[:, 0]][:, None]
    p50p = p50[feas[:, 0]][:, None]
    ok = (sampled & (load[feas] <= Lp - delta_l)
          & (p50[feas] <= p50p - delta_t))
    loadv = jnp.where(ok, load[feas], jnp.inf)
    slot = jnp.argmin(loadv + tie, axis=1)
    want = jnp.take_along_axis(feas, slot[:, None], axis=1)[:, 0]
    want_any = jnp.any(ok, axis=1)
    scal = jnp.asarray([[delta_l, delta_t, 0.0, 0.0]], jnp.float32)
    got, got_any = mr_kernel.route_select(
        feas, load, p50, sampled.astype(jnp.int32), tie, scal,
        mode="midas", tile=tile, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_any), np.asarray(want_any))


def _check_chbl(feas, load, cap, tile):
    """route_select chbl vs the jnp policy expression for one cap."""
    Lf = load[feas]
    under = Lf <= cap
    slot = jnp.where(jnp.any(under, axis=1), jnp.argmax(under, axis=1),
                     jnp.argmin(Lf, axis=1))
    want = jnp.take_along_axis(feas, slot[:, None], axis=1)[:, 0]
    scal = jnp.stack([jnp.zeros(()), jnp.zeros(()), jnp.float32(cap),
                      jnp.zeros(())]).reshape(1, 4)
    got, _ = mr_kernel.route_select(
        feas, load, load, jnp.zeros(feas.shape, jnp.int32),
        jnp.zeros(feas.shape, jnp.float32), scal, mode="chbl", tile=tile,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    return np.asarray(under)


@pytest.mark.parametrize("R,m,d_max,tile", RS_CASES)
def test_route_select_chbl_matches_jnp(R, m, d_max, tile):
    feas, load, _, _ = _rs_inputs(R, m, d_max)
    _check_chbl(feas, load, 1.25 * (jnp.mean(load) + 1.0), tile)


@pytest.mark.parametrize("case", ["none_under", "several_under"])
def test_route_select_chbl_tie_rules(case):
    """The two branches of chbl's slot choice at their tie rules, on
    integer loads so that ties are common: with no slot under the cap
    the least-loaded fallback takes the lowest tied slot, and with
    several under the cap the lowest such slot wins."""
    feas, _, _, _ = _rs_inputs(256, 8, 4)
    load = jnp.asarray([3.0, 1.0, 3.0, 1.0, 5.0, 3.0, 1.0, 5.0])
    cap = 0.0 if case == "none_under" else 3.0
    under = _check_chbl(feas, load, cap, 128)
    if case == "none_under":
        assert not under.any()
    else:
        assert (under.sum(axis=1) >= 2).any()


def test_route_select_rejects_unknown_mode():
    feas, load, _, _ = _rs_inputs(8, 4, 2)
    with pytest.raises(ValueError, match="unknown route mode"):
        mr_kernel.route_select(
            feas, load, load, jnp.zeros(feas.shape, jnp.int32),
            jnp.zeros(feas.shape, jnp.float32),
            jnp.zeros((1, 4), jnp.float32), mode="nope", interpret=True)
