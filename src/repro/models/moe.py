"""Mixture-of-Experts layer with capacity-bounded sort-based dispatch.

Router options:
  * "topk"  — vanilla top-k gating (baseline the paper compares against:
              static placement that ignores load)
  * "midas" — the paper's power-of-d steering over top-(k+d) gate
              candidates using stale per-expert load telemetry (EWMA
              across steps, threaded through the train state exactly like
              the paper's proxy telemetry).

Dispatch is sort-free scatter into an (E, C, d) buffer (capacity
C = ceil(k·T/E · capacity_factor)); tokens over capacity are dropped, and
the drop *rate* is the metadata-hotspot analogue we benchmark: MIDAS
steering lowers it because it routes around hot experts.
"""
from __future__ import annotations

import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ArchConfig
from repro.kernels.midas_route import ops as route_ops
from repro.models.layers import Maker
from repro.sharding.rules import shard


class MoEAux(NamedTuple):
    load: jnp.ndarray        # (E,) this-batch expert token share (mean 1)
    drop_rate: jnp.ndarray   # () fraction of (token, slot) pairs dropped
    steer_rate: jnp.ndarray  # () fraction of slots steered (midas only)
    aux_loss: jnp.ndarray    # () switch-style load-balance loss (topk only)


def moe_init(mk: Maker, cfg: ArchConfig):
    mo = cfg.moe
    d, f, E = cfg.d_model, mo.d_ff_expert, mo.num_experts
    return {
        "router": mk.param((d, E), ("embed", None), fan_in=d),
        "w_gate": mk.param((E, d, f), ("expert", "expert_embed",
                                       "expert_mlp"), fan_in=d),
        "w_up": mk.param((E, d, f), ("expert", "expert_embed",
                                     "expert_mlp"), fan_in=d),
        "w_down": mk.param((E, f, d), ("expert", "expert_mlp",
                                       "expert_embed"), fan_in=f),
    }


def _positions_within_expert(flat_e: jnp.ndarray, E: int) -> jnp.ndarray:
    """pos[i] = #{j < i : e_j == e_i}, vectorized via stable sort."""
    n = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(E))      # first idx per e
    pos_sorted = jnp.arange(n) - start[sorted_e]
    pos = jnp.zeros((n,), jnp.int32).at[order].set(
        pos_sorted.astype(jnp.int32))
    return pos


def _dispatch(cfg: ArchConfig, gate_logits, load_ewma, T, E):
    mo = cfg.moe
    k = mo.experts_per_token
    steered = jnp.zeros((T, k), bool)
    if mo.router == "midas":
        if load_ewma is None:
            load_ewma = jnp.ones((E,), jnp.float32)
        experts, weights, steered = route_ops.midas_dispatch(
            gate_logits, load_ewma, k, mo.midas_d,
            delta_l=float(mo.midas_delta_l), f_max=mo.midas_fmax)
    else:
        experts, weights = route_ops.topk_dispatch(gate_logits, k)
    return experts, weights, steered


def moe_apply_sharded(p, cfg: ArchConfig, x: jnp.ndarray,
                      load_ewma: Optional[jnp.ndarray],
                      ) -> Tuple[jnp.ndarray, MoEAux]:
    """shard_map MoE: the production dispatch path.

    Key facts the XLA SPMD partitioner cannot prove about the einsum path:
    tokens are sharded over the DP axes and REPLICATED over the model axis,
    so every model rank can (a) compute the gate for its local tokens,
    (b) build the dispatch buffer for ITS OWN experts entirely locally
    (no cross-device scatter => kills the TB-scale all-reduces), and
    (c) combine with one small psum of the (T_loc, d) partial outputs over
    the model axis.  Expert weights are all-gathered over the FSDP axes
    explicitly when sharded there ('expert_embed'); with the
    train_ep_resident rule-set they are resident and no gather happens.
    """
    from repro.sharding.rules import current_rules

    rules = current_rules()
    mesh = rules.mesh
    mo = cfg.moe
    B, S, d = x.shape
    E, k, f = mo.num_experts, mo.experts_per_token, mo.d_ff_expert
    tp = mesh.shape["model"]
    E_loc = E // tp
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu

    x_spec = rules.spec("batch", "seq", "embed", shape=x.shape)
    p_specs = {
        "router": rules.spec("embed", None, shape=p["router"].shape),
        "w_gate": rules.spec("expert", "expert_embed", "expert_mlp",
                             shape=p["w_gate"].shape),
        "w_up": rules.spec("expert", "expert_embed", "expert_mlp",
                           shape=p["w_up"].shape),
        "w_down": rules.spec("expert", "expert_mlp", "expert_embed",
                             shape=p["w_down"].shape),
    }
    fsdp_axes = tuple(a for a in (("pod", "data") if "pod" in
                                  mesh.axis_names else ("data",))
                      if p_specs["w_gate"][1] is not None
                      and a in ((p_specs["w_gate"][1],)
                                if isinstance(p_specs["w_gate"][1], str)
                                else tuple(p_specs["w_gate"][1])))
    mlp_ax = p_specs["w_gate"][2]
    partial_f_axes = tuple((mlp_ax,) if isinstance(mlp_ax, str)
                           else (mlp_ax or ()))
    partial_f = bool(partial_f_axes)

    def local(px, xl, load):
        # xl: (B_loc, S, d) local tokens (replicated over model)
        Bl, Sl, _ = xl.shape
        Tl = Bl * Sl
        xt = xl.reshape(Tl, d)
        if partial_f:
            # weight-stationary path: replicate the (tiny) token batch
            # across the f-sharding axes so partial-weight results can be
            # psum'd soundly
            xt = jax.lax.all_gather(xt, partial_f_axes, axis=0, tiled=True)
            Tl = xt.shape[0]
        logits = jnp.einsum("td,de->te", xt, px["router"]).astype(
            jnp.float32)
        experts, weights, steered = _dispatch(cfg, logits, load, Tl, E)

        C = max(int(-(-k * Tl // E) * mo.capacity_factor), 1)
        C = min(C, Tl)
        flat_e = experts.reshape(Tl * k)
        flat_w = weights.reshape(Tl * k)
        pos = _positions_within_expert(flat_e, E)
        keep = pos < C
        rank = jax.lax.axis_index("model")
        mine = (flat_e // E_loc) == rank
        e_loc = jnp.where(keep & mine, flat_e - rank * E_loc, E_loc)
        tok_idx = jnp.repeat(jnp.arange(Tl), k)

        buf = jnp.zeros((E_loc, C, d), xt.dtype)
        buf = buf.at[e_loc, pos].add(xt[tok_idx], mode="drop")

        wg, wu, wd = px["w_gate"], px["w_up"], px["w_down"]
        if partial_f:
            # weight-stationary decode path (rule-sets sharding
            # 'expert_mlp' over the DP axes): experts stay RESIDENT as
            # f-chunks; gated act is elementwise in f so g/u need no
            # collective; only the (E_loc, C, d) down-proj partials are
            # psum'd — tiny when C is a decode-sized capacity.  NOTE:
            # tokens were all-gathered over the DP axes up front (see
            # above), so every rank holds the SAME tokens and the psum is
            # sound — partial-weight math with rank-distinct tokens is
            # NOT (that failed the oracle check and was removed).
            g = jnp.einsum("ecd,edf->ecf", buf, wg)
            u = jnp.einsum("ecd,edf->ecf", buf, wu)
            out = jax.lax.psum(jnp.einsum("ecf,efd->ecd", act(g) * u, wd),
                               partial_f_axes)
        else:
            for ax in fsdp_axes:        # explicit FSDP gather (bf16)
                wg = jax.lax.all_gather(wg, ax, axis=1, tiled=True)
                wu = jax.lax.all_gather(wu, ax, axis=1, tiled=True)
                wd = jax.lax.all_gather(wd, ax, axis=2, tiled=True)
            g = jnp.einsum("ecd,edf->ecf", buf, wg)
            u = jnp.einsum("ecd,edf->ecf", buf, wu)
            out = jnp.einsum("ecf,efd->ecd", act(g) * u, wd)

        gathered = out[jnp.minimum(e_loc, E_loc - 1), pos]
        gathered = jnp.where((keep & mine)[:, None], gathered, 0.0)
        y = (gathered.astype(jnp.float32) * flat_w[:, None]
             ).reshape(Tl, k, d).sum(axis=1)
        y = jax.lax.psum(y.astype(xl.dtype), "model")
        if partial_f:
            idx = jnp.zeros((), jnp.int32)
            for ax in partial_f_axes:
                idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
            y = jax.lax.dynamic_slice_in_dim(y, idx * Bl * Sl, Bl * Sl,
                                             axis=0)

        axes = tuple(mesh.axis_names)      # replicate stats on all devices
        load_out = jax.lax.pmean(route_ops.expert_load(experts, E), axes)
        drop = jax.lax.pmean(1.0 - keep.mean(), axes)
        steer = jax.lax.pmean(steered.mean(), axes)
        probs = jax.nn.softmax(logits, axis=-1)
        aux_l = E * jnp.sum(load_out / E * jax.lax.pmean(
            probs.mean(axis=0), axes))
        return (y.reshape(Bl, Sl, d),
                MoEAux(load=load_out, drop_rate=drop, steer_rate=steer,
                       aux_loss=aux_l))

    from jax.sharding import PartitionSpec as P
    out_specs = (x_spec, MoEAux(load=P(), drop_rate=P(), steer_rate=P(),
                                aux_loss=P()))
    mapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(p_specs, x_spec, P()),
        out_specs=out_specs,
        check_vma=False,
    )
    y, aux = mapped(p, x, load_ewma if load_ewma is not None
                    else jnp.ones((E,), jnp.float32))
    return y, aux


def moe_apply(p, cfg: ArchConfig, x: jnp.ndarray,
              load_ewma: Optional[jnp.ndarray] = None,
              ) -> Tuple[jnp.ndarray, MoEAux]:
    """x: (B, S, d).  load_ewma: (E,) stale telemetry (midas router)."""
    from repro.sharding.rules import current_rules
    rules = current_rules()
    if (rules is not None and rules.mesh is not None
            and cfg.moe.num_experts % rules.mesh.shape.get("model", 1) == 0
            and os.environ.get("REPRO_MOE_EINSUM") != "1"):
        return moe_apply_sharded(p, cfg, x, load_ewma)
    mo = cfg.moe
    B, S, d = x.shape
    E, k, f = mo.num_experts, mo.experts_per_token, mo.d_ff_expert
    T = B * S
    xt = x.reshape(T, d)

    gate_logits = jnp.einsum("td,de->te", xt, p["router"]).astype(
        jnp.float32)
    experts, weights, steered = _dispatch(cfg, gate_logits, load_ewma, T, E)

    # ---- capacity-bounded dispatch -----------------------------------
    C = max(int(-(-k * T // E) * mo.capacity_factor), 1)
    C = min(C, T)
    flat_e = experts.reshape(T * k)
    flat_w = weights.reshape(T * k)
    pos = _positions_within_expert(flat_e, E)
    keep = pos < C
    e_or_drop = jnp.where(keep, flat_e, E)                 # OOB => dropped
    tok_idx = jnp.repeat(jnp.arange(T), k)

    buf = jnp.zeros((E, C, d), xt.dtype)
    buf = buf.at[e_or_drop, pos].add(xt[tok_idx], mode="drop")
    buf = shard(buf, "expert", None, "embed")

    # ---- expert FFN (gated) -------------------------------------------
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = shard(act(g) * u, "expert", None, "expert_mlp")
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    out = shard(out, "expert", None, "embed")

    # ---- combine -------------------------------------------------------
    gathered = out[jnp.minimum(e_or_drop, E - 1), pos]     # (T*k, d)
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    wsum = (gathered.astype(jnp.float32)
            * flat_w[:, None]).reshape(T, k, d).sum(axis=1)
    y = wsum.astype(x.dtype).reshape(B, S, d)
    y = shard(y, "batch", "seq", "embed")

    # ---- aux -------------------------------------------------------------
    load = route_ops.expert_load(experts, E)
    drop_rate = 1.0 - keep.mean()
    # switch-transformer aux loss (only meaningful for the topk baseline)
    probs = jax.nn.softmax(gate_logits, axis=-1)
    frac_tokens = load / E
    frac_probs = probs.mean(axis=0)
    aux_loss = E * jnp.sum(frac_tokens * frac_probs)
    return y, MoEAux(load=load, drop_rate=drop_rate,
                     steer_rate=steered.mean(), aux_loss=aux_loss)


def update_load_ewma(load_ewma: jnp.ndarray, batch_load: jnp.ndarray,
                     alpha: float = 0.2) -> jnp.ndarray:
    """Paper's fast-loop EWMA over (stale) telemetry."""
    return (1.0 - alpha) * load_ewma + alpha * batch_load
