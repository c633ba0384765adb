"""Pallas TPU kernels for MIDAS routing — MoE dispatch AND engine waves.

This module is the repo's single routing kernel (DESIGN.md §15).  Two
entry points share the VMEM-tiled building blocks:

* :func:`midas_dispatch` — MoE expert dispatch (top-(k+d) + power-of-d
  steer).  Grid: token tiles; the (tile, E) gate-logit block and the
  (E,) load telemetry live in VMEM; top-(k+d) selection is k+d iterated
  argmax/mask passes (k+d <= 16 for all assigned archs — cheaper than a
  full sort on the VPU).  Ragged ``T`` is handled by padding the last
  tile (rows are independent, pads are sliced off).

  The f_max-capped variant runs as a TWO-PASS grid: pass 1 is the tiled
  candidate kernel (:func:`_cand_body`, the O(T·E·(k+d)) selection —
  all the arithmetic intensity); the global f_max quantile is a
  cross-tile reduction over the per-tile partials, so it runs between
  the passes as one XLA sort over the (T,) benefit vector; pass 2 (the
  slot-sequential steering, O(T·(k+d)) elementwise) shares
  ``ref.steer_from_candidates`` with the reference — which is what
  makes ref-vs-kernel parity bitwise rather than approximate.  With
  ``f_max >= 1`` (margin-governed) the single-pass kernel steers
  entirely in VMEM, as before.

* :func:`route_select` — the simulator's per-wave routing core: the
  feasible-set load gather + eligibility masking + tie-broken argmin
  that ``power_of_d`` / ``midas`` / ``chbl`` all reduce to.  Grid:
  request tiles; the (m,) telemetry views sit in VMEM and gathers are
  one-hot contractions (m <= a few hundred servers).  RNG stays
  OUTSIDE the kernel: the engine passes the exact ``jax.random``
  sampling masks and tie-break scores the jnp policies draw, so the
  kernel path is bit-for-bit the reference policy — the golden-parity
  contract extends to ``SimConfig(route_impl="pallas")``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -2.0**30

# engine-wave routing modes served by route_select (the three policies
# whose inner loop is gather + mask + argmin)
ROUTE_MODES = ("power_of_d", "midas", "chbl")


def _first_true(mask, n: int):
    """Lowest index along the last axis where ``mask`` holds (``n`` when
    none does).  Every arg-reduction in this module is spelled with it
    (``_first_true(x == min(x))`` is ``argmin``): Mosaic's own
    arg-reductions take float32 only and promise no tie order, while
    bitwise parity with ``ref`` needs jnp's lowest-index rule."""
    cols = jax.lax.broadcasted_iota(jnp.int32, mask.shape, mask.ndim - 1)
    return jnp.min(jnp.where(mask, cols, n), axis=-1)


def _argmin(x):
    """``jnp.argmin`` over the last axis, lowest index on ties."""
    return _first_true(x == jnp.min(x, axis=-1, keepdims=True), x.shape[-1])


def _iter_topk(logits, kd: int, tile: int, E: int):
    """Top-``kd`` ids/vals per row via iterated argmax/mask (VPU-friendly;
    ties resolve to the lowest index, matching ``jax.lax.top_k``)."""
    masked = logits
    ids = []
    vals = []
    for _ in range(kd):
        val = jnp.max(masked, axis=-1)
        idx = _first_true(masked == val[:, None], E)  # (tile,)
        cols = jax.lax.broadcasted_iota(jnp.int32, (tile, E), 1)
        masked = jnp.where(cols == idx[:, None], NEG_INF, masked)
        ids.append(idx)
        vals.append(val)
    return jnp.stack(ids, axis=1), jnp.stack(vals, axis=1)


def _take_1h(load, ids, E: int):
    """``load[ids]`` for a (E,) VMEM vector as a one-hot contraction over
    an E-wide iota (Mosaic lowers no vector-indexed gather); exact, since
    each row sums one value and zeros."""
    iota = jax.lax.broadcasted_iota(jnp.int32, ids.shape + (E,), ids.ndim)
    return jnp.sum(jnp.where(ids[..., None] == iota, load, 0.0), axis=-1)


def _body(
    logits_ref,
    load_ref,
    experts_ref,
    weights_ref,
    steered_ref,
    *,
    k: int,
    d: int,
    delta_l: float,
    gate_slack: float,
    E: int,
    tile: int,
):
    logits = logits_ref[...].astype(jnp.float32)  # (tile, E)
    load = load_ref[...].astype(jnp.float32)  # (1, E)
    load = load[0]

    # --- top-(k+d) via iterated argmax ---------------------------------
    cand, cvals = _iter_topk(logits, k + d, tile, E)  # (tile, k+d)

    alt_ids = cand[:, k:]  # (tile, d)
    alt_vals = cvals[:, k:]
    alt_load = _take_1h(load, alt_ids, E)
    alt_used = jnp.zeros((tile, d), jnp.bool_)

    chosen_e = []
    chosen_v = []
    steer_fl = []
    for i in range(k):
        prim = cand[:, i]
        prim_val = cvals[:, i]
        prim_load = _take_1h(load, prim, E)
        ok = (
            ~alt_used
            & (alt_load <= prim_load[:, None] - delta_l)
            & (alt_vals >= prim_val[:, None] - gate_slack)
        )
        a_load = jnp.where(ok, alt_load, jnp.inf)
        best = _argmin(a_load)
        has = jnp.any(ok, axis=-1)
        benefit = jnp.where(
            has,
            prim_load - jnp.min(a_load, axis=-1),
            -jnp.inf,
        )
        steer = has & (benefit >= delta_l)
        cols = jax.lax.broadcasted_iota(jnp.int32, (tile, d), 1)
        sel = cols == best[:, None]
        sel_id = jnp.sum(jnp.where(sel, alt_ids, 0), axis=1)
        sel_val = jnp.sum(jnp.where(sel, alt_vals, 0.0), axis=1)
        e_i = jnp.where(steer, sel_id, prim)
        v_i = jnp.where(steer, sel_val, prim_val)
        alt_used = alt_used | (steer[:, None] & sel)
        chosen_e.append(e_i)
        chosen_v.append(v_i)
        steer_fl.append(steer.astype(jnp.int32))

    ce = jnp.stack(chosen_e, axis=1)
    cv = jnp.stack(chosen_v, axis=1)
    # softmax over chosen logits
    mx = jnp.max(cv, axis=1, keepdims=True)
    ex = jnp.exp(cv - mx)
    w = ex / jnp.sum(ex, axis=1, keepdims=True)

    experts_ref[...] = ce.astype(jnp.int32)
    weights_ref[...] = w.astype(jnp.float32)
    steered_ref[...] = jnp.stack(steer_fl, axis=1)


def _cand_body(logits_ref, cand_ref, vals_ref, *, kd: int, E: int, tile: int):
    """Pass 1 of the f_max-capped variant: top-(k+d) candidates only."""
    logits = logits_ref[...].astype(jnp.float32)
    cand, cvals = _iter_topk(logits, kd, tile, E)
    cand_ref[...] = cand
    vals_ref[...] = cvals.astype(jnp.float32)


def _pad_rows(x, rows: int):
    """Zero-pad axis 0 to ``rows`` (no-op when already there)."""
    if x.shape[0] == rows:
        return x
    return jnp.pad(x, ((0, rows - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


@functools.partial(
    jax.jit,
    static_argnames=(
        "k",
        "d",
        "delta_l",
        "gate_slack",
        "f_max",
        "tile",
        "interpret",
    ),
)
def midas_dispatch(
    gate_logits,
    load,
    k: int,
    d: int,
    *,
    delta_l: float = 2.0,
    gate_slack: float = 1.0,
    f_max: float = 1.0,
    tile: int = 256,
    interpret: bool = False,
):
    """MIDAS MoE dispatch (margin-governed AND f_max-capped variants)."""
    T, E = gate_logits.shape
    d_eff = min(d, E - k)
    if d_eff <= 0:
        from repro.kernels.midas_route import ref

        e, w = ref.topk_dispatch(gate_logits, k)
        return e, w, jnp.zeros_like(e, dtype=bool)
    tl = min(tile, T)
    Tp = -(-T // tl) * tl  # rows are independent: pad ragged last tile
    logits_p = _pad_rows(gate_logits.astype(jnp.float32), Tp)
    load2 = load[None].astype(jnp.float32)

    if f_max < 1.0:
        # two-pass grid: tiled candidate kernel, then the cross-tile
        # quantile + steering shared with the reference (module docstring)
        from repro.kernels.midas_route import ref

        kernel = functools.partial(_cand_body, kd=k + d_eff, E=E, tile=tl)
        cand, cvals = pl.pallas_call(
            kernel,
            grid=(Tp // tl,),
            in_specs=[pl.BlockSpec((tl, E), lambda i: (i, 0))],
            out_specs=[
                pl.BlockSpec((tl, k + d_eff), lambda i: (i, 0)),
                pl.BlockSpec((tl, k + d_eff), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((Tp, k + d_eff), jnp.int32),
                jax.ShapeDtypeStruct((Tp, k + d_eff), jnp.float32),
            ],
            interpret=interpret,
            name="midas_dispatch_candidates",
        )(logits_p)
        return ref.steer_from_candidates(
            cand[:T],
            cvals[:T],
            load,
            k,
            delta_l=delta_l,
            gate_slack=gate_slack,
            f_max=f_max,
        )

    kernel = functools.partial(
        _body,
        k=k,
        d=d_eff,
        delta_l=delta_l,
        gate_slack=gate_slack,
        E=E,
        tile=tl,
    )
    experts, weights, steered = pl.pallas_call(
        kernel,
        grid=(Tp // tl,),
        in_specs=[
            pl.BlockSpec((tl, E), lambda i: (i, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tl, k), lambda i: (i, 0)),
            pl.BlockSpec((tl, k), lambda i: (i, 0)),
            pl.BlockSpec((tl, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Tp, k), jnp.int32),
            jax.ShapeDtypeStruct((Tp, k), jnp.float32),
            jax.ShapeDtypeStruct((Tp, k), jnp.int32),
        ],
        interpret=interpret,
        name="midas_dispatch_onepass",
    )(logits_p, load2)
    return experts[:T], weights[:T], steered[:T].astype(bool)


# ---------------------------------------------------------------------------
# Engine wave routing (simulator hot path)
# ---------------------------------------------------------------------------


def _route_body(
    feas_ref,
    samp_ref,
    tie_ref,
    load_ref,
    p50_ref,
    scal_ref,
    assign_ref,
    okany_ref,
    *,
    mode: str,
    m: int,
    d_max: int,
    tile: int,
):
    """One request tile of wave routing.

    The (m,) telemetry views live whole in VMEM; the feasible-set load
    gather is a one-hot contraction (TPU-friendly for small m).  Every
    comparison/argmin mirrors the jnp policy expression for the same
    inputs, so results are bitwise identical to the reference path.
    """
    feas = feas_ref[...]  # (tile, d_max)
    samp = samp_ref[...] != 0
    tie = tie_ref[...].astype(jnp.float32)
    load = load_ref[...].astype(jnp.float32)[0]  # (m,)

    lf = _take_1h(load, feas, m)  # L_view[feas]

    ok_any = jnp.zeros((tile,), jnp.int32)
    if mode == "power_of_d":
        loadv = jnp.where(samp, lf, jnp.inf)
        slot = _argmin(loadv + tie)
    elif mode == "midas":
        p50 = p50_ref[...].astype(jnp.float32)[0]
        p50f = _take_1h(p50, feas, m)
        delta_l = scal_ref[0, 0]
        delta_t = scal_ref[0, 1]
        # slot 0 IS the primary: lf[:, :1] == L_view[feas[:, 0]]
        ok = (
            samp
            & (lf <= lf[:, :1] - delta_l)
            & (p50f <= p50f[:, :1] - delta_t)
        )
        loadv = jnp.where(ok, lf, jnp.inf)
        slot = _argmin(loadv + tie)
        ok_any = jnp.any(ok, axis=-1).astype(jnp.int32)
    elif mode == "chbl":
        cap = scal_ref[0, 2]
        first_under = _first_true(lf <= cap, d_max)
        slot = jnp.where(first_under < d_max, first_under, _argmin(lf))
    else:  # pragma: no cover - guarded by route_select
        raise ValueError(f"unknown route mode {mode!r}")

    cols = jax.lax.broadcasted_iota(jnp.int32, (tile, d_max), 1)
    assign = jnp.sum(jnp.where(cols == slot[:, None], feas, 0), axis=-1)
    assign_ref[...] = assign[:, None].astype(jnp.int32)
    okany_ref[...] = ok_any[:, None]


@functools.partial(jax.jit, static_argnames=("mode", "tile", "interpret"))
def route_select(
    feas,
    load,
    p50,
    sampled,
    tie,
    scalars,
    *,
    mode: str,
    tile: int = 128,
    interpret: bool = False,
):
    """Wave-routing core: per-request best feasible server.

    feas: (R, d_max) int32 feasible sets (slot 0 = primary); load/p50:
    (m,) telemetry views; sampled: (R, d_max) int32 0/1 power-of-d
    sampling mask (host-drawn, ignored by chbl); tie: (R, d_max) f32
    tie-break scores (host-drawn); scalars: (1, 4) f32 packed traced
    scalars [delta_l, delta_t, cap, unused].  Returns
    ``(assign (R,) int32, ok_any (R,) bool)`` — ``ok_any`` is midas's
    per-request "any eligible candidate" flag (False elsewhere).
    """
    if mode not in ROUTE_MODES:
        raise ValueError(
            f"unknown route mode {mode!r}; available: "
            f"{', '.join(ROUTE_MODES)}"
        )
    R, d_max = feas.shape
    m = load.shape[0]
    tl = min(tile, R)
    Rp = -(-R // tl) * tl  # requests are independent: pad the last tile
    kernel = functools.partial(
        _route_body,
        mode=mode,
        m=m,
        d_max=d_max,
        tile=tl,
    )
    assign, ok_any = pl.pallas_call(
        kernel,
        grid=(Rp // tl,),
        in_specs=[
            pl.BlockSpec((tl, d_max), lambda i: (i, 0)),
            pl.BlockSpec((tl, d_max), lambda i: (i, 0)),
            pl.BlockSpec((tl, d_max), lambda i: (i, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
            pl.BlockSpec((1, 4), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tl, 1), lambda i: (i, 0)),
            pl.BlockSpec((tl, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, 1), jnp.int32),
            jax.ShapeDtypeStruct((Rp, 1), jnp.int32),
        ],
        interpret=interpret,
        name="route_select",
    )(
        _pad_rows(feas.astype(jnp.int32), Rp),
        _pad_rows(sampled.astype(jnp.int32), Rp),
        _pad_rows(tie.astype(jnp.float32), Rp),
        load[None].astype(jnp.float32),
        p50[None].astype(jnp.float32),
        scalars.astype(jnp.float32),
    )
    return assign[:R, 0], ok_any[:R, 0].astype(bool)
