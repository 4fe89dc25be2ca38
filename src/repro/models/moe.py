"""Mixture-of-Experts: the LM-scale incarnation of the paper's Step-4
sparsity-aware primitive mapping.

Top-k routing makes the token->expert assignment a block-sparse matrix.
Two realizations are provided, mirroring the DDMM/SpDMM choice:

  dense  every expert runs on every token, weighted by the (mostly-zero)
         gate matrix — the uniform DDMM mapping. FLOPs scale with
         n_experts/top_k (32x for DeepSeek-V3), but the program is pure
         einsum and shards trivially (used for smoke tests and for small-E
         archs like grok-1 where expert weights are TP-sharded over d_ff
         and the blow-up is 4x).

  a2a    explicit expert-parallel dispatch under shard_map: tokens are
         routed to the expert-owner shard with one all_to_all, batched per
         local expert (fixed capacity, Switch-style cumsum positioning),
         and returned with a second all_to_all — the SpDMM mapping whose
         cost follows nnz (= tokens * top_k), not the dense t*E product.
         Requires n_experts % model_axis_size == 0.

The Step-4 decision (configs set ``MoEConfig.impl``) follows the same cost
model logic as core/passes/select.py: dense costs t*E*d*ff, sparse costs
t*k*d*ff*overhead — with E/k = 32 the sparse mapping wins by >10x; with
E/k = 4 (grok) the a2a overhead and EP imbalance make dense-TP competitive.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import init_linear, init_mlp, mlp_apply


def init_moe(key, cfg, dtype):
    mo = cfg.moe
    d, ff = cfg.d_model, mo.d_ff_expert
    ks = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(d)

    def experts(k):
        return (jax.random.normal(k, (mo.n_experts, d, ff), jnp.float32)
                * scale).astype(dtype)

    p = {"router": init_linear(ks[0], d, mo.n_experts, dtype),
         "wi": experts(ks[1]), "wg": experts(ks[2]),
         "wo": (jax.random.normal(ks[3], (mo.n_experts, ff, d), jnp.float32)
                * (1.0 / math.sqrt(ff))).astype(dtype)}
    if mo.n_shared:
        p["shared"] = init_mlp(ks[4], d, ff * mo.n_shared, dtype,
                               cfg.mlp_act)
    return p


def _route(params, t, mo):
    """t (T, d) -> (weights (T,k), ids (T,k), probs (T,E))."""
    logits = jnp.einsum("td,de->te", t.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    if mo.router == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, mo.top_k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    return topw, topi, probs


def aux_load_balance_loss(probs, topi, n_experts: int, *, axes=()):
    """Switch-style load-balancing loss (fraction * probability).

    ``axes``: mesh axes to pmean the per-token statistics over BEFORE the
    product — the loss is bilinear in (me, ce), so averaging the loss
    itself across token shards would NOT equal the global-batch loss."""
    me = probs.mean(0)
    ce = jax.nn.one_hot(topi, n_experts).sum(1).mean(0)
    if axes:
        me = jax.lax.pmean(me, axes)
        ce = jax.lax.pmean(ce, axes)
    return n_experts * jnp.sum(me * ce)


# ------------------------------------------------------------ dense path --
def moe_dense(params, x, cfg):
    mo = cfg.moe
    d = cfg.d_model
    t = x.reshape(-1, d)
    topw, topi, probs = _route(params, t, mo)
    gates = (jax.nn.one_hot(topi, mo.n_experts, dtype=jnp.float32)
             * topw[..., None]).sum(1)                       # (T, E)
    h = jnp.einsum("td,edf->tef", t, params["wg"],
                   preferred_element_type=jnp.float32)
    h = jax.nn.silu(h) * jnp.einsum("td,edf->tef", t, params["wi"],
                                    preferred_element_type=jnp.float32)
    h = (h * gates[:, :, None]).astype(x.dtype)
    out = jnp.einsum("tef,efd->td", h, params["wo"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    if mo.n_shared:
        out = out + mlp_apply(params["shared"], t, cfg.mlp_act)
    aux = aux_load_balance_loss(probs, topi, mo.n_experts)
    return out.reshape(x.shape), aux


# -------------------------------------------------------------- a2a path --
def _moe_a2a_local(params, x, cfg, axis: str, dp_axes=("data",)):
    """Runs per-device under shard_map. x: (B_loc, S_loc, d)."""
    mo = cfg.moe
    d = cfg.d_model
    M = jax.lax.axis_size(axis)
    e_loc = mo.n_experts // M
    t = x.reshape(-1, d)
    T = t.shape[0]
    topw, topi, probs = _route(params, t, mo)

    eid = topi.reshape(-1)                        # (T*k,)
    w = topw.reshape(-1).astype(jnp.float32)
    src = jnp.arange(T * mo.top_k) // mo.top_k
    dest = eid // e_loc                           # owner shard
    # Switch-style position: rank of each entry within its destination
    oh = jax.nn.one_hot(dest, M, dtype=jnp.int32)
    pos = (jnp.cumsum(oh, axis=0) - oh)[jnp.arange(dest.size), dest]
    cap = int(math.ceil(T * mo.top_k / M * mo.capacity_factor))
    cap = -(-cap // 8) * 8
    keep = pos < cap

    send_x = jnp.zeros((M, cap, d), x.dtype).at[dest, pos].set(
        t[src], mode="drop")
    send_e = jnp.full((M, cap), -1, jnp.int32).at[dest, pos].set(
        eid % e_loc, mode="drop")
    recv_x = jax.lax.all_to_all(send_x, axis, 0, 0, tiled=False)
    recv_e = jax.lax.all_to_all(send_e, axis, 0, 0, tiled=False)

    # local expert compute: scatter into per-expert buffers
    rt = recv_x.reshape(-1, d)                    # (M*cap, d)
    re = recv_e.reshape(-1)
    n_in = rt.shape[0]
    cap2 = -(-int(math.ceil(n_in / max(e_loc, 1)
                            * mo.capacity_factor)) // 8) * 8
    oh2 = jax.nn.one_hot(re, e_loc, dtype=jnp.int32)
    pos2 = (jnp.cumsum(oh2, axis=0) - oh2)[
        jnp.arange(n_in), jnp.clip(re, 0)]
    valid2 = (re >= 0) & (pos2 < cap2)
    xbuf = jnp.zeros((e_loc, cap2, d), x.dtype).at[
        jnp.where(valid2, re, e_loc), pos2].set(rt, mode="drop")
    # local expert weights (shard_map gives the e_loc slice)
    h = jnp.einsum("ecd,edf->ecf", xbuf, params["wg"],
                   preferred_element_type=jnp.float32)
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", xbuf, params["wi"],
                                    preferred_element_type=jnp.float32)
    yb = jnp.einsum("ecf,efd->ecd", h.astype(x.dtype), params["wo"],
                    preferred_element_type=jnp.float32).astype(x.dtype)
    y = yb[jnp.where(valid2, re, 0), pos2] * valid2[:, None]
    send_back = y.reshape(M, cap, d)
    recv_back = jax.lax.all_to_all(send_back, axis, 0, 0, tiled=False)

    contrib = recv_back[dest, pos] * (keep * w)[:, None]
    out = jax.ops.segment_sum(contrib.astype(jnp.float32), src, T)
    out = out.astype(x.dtype)
    if mo.n_shared:
        out = out + mlp_apply(params["shared"], t, cfg.mlp_act)
    aux = aux_load_balance_loss(probs, topi, mo.n_experts,
                                axes=tuple(dp_axes) + (axis,))
    return out.reshape(x.shape), aux


def moe_a2a(params, x, cfg, *, mesh, dp_axes=("data",), model_axis="model"):
    """shard_map wrapper: x (B, S, d) B sharded over dp_axes, S over model.
    Expert weights sharded over ``model_axis`` on dim 0; router/shared
    replicated."""
    mo = cfg.moe
    espec = {"router": P(), "wi": P(model_axis), "wg": P(model_axis),
             "wo": P(model_axis)}
    if mo.n_shared:
        espec["shared"] = jax.tree.map(lambda _: P(), params["shared"])
    fn = partial(_moe_a2a_local, cfg=cfg, axis=model_axis, dp_axes=dp_axes)
    out, aux = jax.shard_map(
        lambda p, xx: fn(p, xx),
        mesh=mesh,
        in_specs=(espec, P(dp_axes, model_axis, None)),
        out_specs=(P(dp_axes, model_axis, None), P()),
        check_vma=False,
    )(params, x)
    return out, aux


# --------------------------------------------------------- gathered path --
def _moe_gathered_local(params, x, cfg, axis: str, dp_axes=("data",)):
    """Decode-path EP: x is *replicated* over the model axis (T tokens are
    too few to all_to_all); each rank selects the (token, expert) pairs
    owned by its local expert slice, computes them at SpDMM cost
    (~T·k/M pairs), and the outputs are psum-combined.

    Runs per-device under shard_map. x: (B, S, d) with B/S unsharded on
    ``axis``; expert weights sharded on dim 0."""
    mo = cfg.moe
    d = cfg.d_model
    M = jax.lax.axis_size(axis)
    ridx = jax.lax.axis_index(axis)
    e_loc = mo.n_experts // M
    t = x.reshape(-1, d)
    T = t.shape[0]
    topw, topi, probs = _route(params, t, mo)     # replicated -> identical
    eid = topi.reshape(-1)
    w = topw.reshape(-1).astype(jnp.float32)
    src = jnp.arange(T * mo.top_k) // mo.top_k
    is_local = (eid // e_loc) == ridx
    le = eid % e_loc
    # capacity buffer of local pairs
    cap = int(math.ceil(T * mo.top_k / M * mo.capacity_factor))
    cap = max(-(-cap // 8) * 8, 8)
    pos = jnp.cumsum(is_local.astype(jnp.int32)) - 1
    keep = is_local & (pos < cap)
    slot = jnp.where(keep, pos, cap)
    xbuf = jnp.zeros((cap, d), x.dtype).at[slot].set(t[src], mode="drop")
    ebuf = jnp.zeros((cap,), jnp.int32).at[slot].set(le, mode="drop")
    # per-pair expert weights via one-hot DDMM against the local slice
    oh = jax.nn.one_hot(ebuf, e_loc, dtype=x.dtype)       # (cap, e_loc)
    wg = jnp.einsum("ce,edf->cdf", oh, params["wg"])
    wi = jnp.einsum("ce,edf->cdf", oh, params["wi"])
    wo = jnp.einsum("ce,efd->cfd", oh, params["wo"])
    h = jnp.einsum("cd,cdf->cf", xbuf, wg,
                   preferred_element_type=jnp.float32)
    h = jax.nn.silu(h) * jnp.einsum("cd,cdf->cf", xbuf, wi,
                                    preferred_element_type=jnp.float32)
    y = jnp.einsum("cf,cfd->cd", h.astype(x.dtype), wo,
                   preferred_element_type=jnp.float32)    # (cap, d)
    contrib = y[slot] * (keep * w)[:, None]
    out = jax.ops.segment_sum(contrib, src, T)
    out = jax.lax.psum(out.astype(jnp.float32), axis).astype(x.dtype)
    if mo.n_shared:
        out = out + mlp_apply(params["shared"], t, cfg.mlp_act)
    aux = aux_load_balance_loss(probs, topi, mo.n_experts,
                                axes=tuple(dp_axes))
    return out.reshape(x.shape), aux


def moe_gathered(params, x, cfg, *, mesh, dp_axes=("data",),
                 model_axis="model"):
    """shard_map wrapper for the decode path: x (B,1,d), B over dp_axes,
    replicated over model; experts sharded over model dim 0."""
    mo = cfg.moe
    espec = {"router": P(), "wi": P(model_axis), "wg": P(model_axis),
             "wo": P(model_axis)}
    if mo.n_shared:
        espec["shared"] = jax.tree.map(lambda _: P(), params["shared"])
    fn = partial(_moe_gathered_local, cfg=cfg, axis=model_axis,
                 dp_axes=dp_axes)
    out, aux = jax.shard_map(
        lambda p, xx: fn(p, xx),
        mesh=mesh,
        in_specs=(espec, P(dp_axes, None, None)),
        out_specs=(P(dp_axes, None, None), P()),
        check_vma=False,
    )(params, x)
    return out, aux


# ------------------------------------------------------- 2-D gathered path --
def _moe_gathered2d_local(params, x, cfg, model_axis: str, fsdp_axis):
    """Decode EP without the ZeRO-3 weight regather (§Perf iteration 5).

    Expert weights stay sharded on BOTH axes — experts over ``model_axis``,
    d_model over ``fsdp_axis`` — and the (few) token vectors are replicated
    instead: each (fsdp, model) rank computes its d-slice of its local
    experts and the partial products are psum-combined. Collective volume
    per layer drops from O(expert_weight_bytes) (the all-gather this
    replaces) to O(tokens x d_ff) — for 128 decode tokens a ~300x cut.

    x: (B, S, d) fully replicated; out replicated.
    """
    mo = cfg.moe
    d = cfg.d_model
    M = jax.lax.axis_size(model_axis)
    ridx = jax.lax.axis_index(model_axis)
    D = jax.lax.axis_size(fsdp_axis) if isinstance(fsdp_axis, str) else 1
    e_loc = mo.n_experts // M
    t = x.reshape(-1, d)
    T = t.shape[0]
    topw, topi, probs = _route(params, t, mo)     # replicated -> identical
    eid = topi.reshape(-1)
    w = topw.reshape(-1).astype(jnp.float32)
    src = jnp.arange(T * mo.top_k) // mo.top_k
    is_local = (eid // e_loc) == ridx
    le = eid % e_loc
    cap = int(math.ceil(T * mo.top_k / M * mo.capacity_factor))
    cap = max(-(-cap // 8) * 8, 8)
    pos = jnp.cumsum(is_local.astype(jnp.int32)) - 1
    keep = is_local & (pos < cap)
    slot = jnp.where(keep, pos, cap)
    xbuf = jnp.zeros((cap, d), x.dtype).at[slot].set(t[src], mode="drop")
    ebuf = jnp.zeros((cap,), jnp.int32).at[slot].set(le, mode="drop")
    oh = jax.nn.one_hot(ebuf, e_loc, dtype=x.dtype)       # (cap, e_loc)
    # local d-slice of the tokens vs d-sharded expert weights
    d_loc = params["wg"].shape[1]                 # d // D under shard_map
    didx = jax.lax.axis_index(fsdp_axis) if D > 1 else 0
    xsl = jax.lax.dynamic_slice_in_dim(xbuf, didx * d_loc, d_loc, 1)
    wg = jnp.einsum("ce,edf->cdf", oh, params["wg"])
    wi = jnp.einsum("ce,edf->cdf", oh, params["wi"])
    hg = jnp.einsum("cd,cdf->cf", xsl, wg,
                    preferred_element_type=jnp.float32)
    hi = jnp.einsum("cd,cdf->cf", xsl, wi,
                    preferred_element_type=jnp.float32)
    if D > 1:
        hg = jax.lax.psum(hg, fsdp_axis)
        hi = jax.lax.psum(hi, fsdp_axis)
    h = (jax.nn.silu(hg) * hi).astype(x.dtype)            # (cap, ff)
    wo = jnp.einsum("ce,efd->cfd", oh, params["wo"])      # (cap, ff, d_loc)
    y_loc = jnp.einsum("cf,cfd->cd", h, wo,
                       preferred_element_type=jnp.float32)
    if D > 1:
        y = jax.lax.all_gather(y_loc, fsdp_axis, axis=1, tiled=True)
    else:
        y = y_loc                                          # (cap, d)
    contrib = y[slot] * (keep * w)[:, None]
    out = jax.ops.segment_sum(contrib, src, T)
    out = jax.lax.psum(out.astype(jnp.float32), model_axis).astype(x.dtype)
    if mo.n_shared:
        out = out + mlp_apply(params["shared"], t, cfg.mlp_act)
    aux = aux_load_balance_loss(probs, topi, mo.n_experts)
    return out.reshape(x.shape), aux


def moe_gathered2d(params, x, cfg, *, mesh, dp_axes=("data",),
                   model_axis="model"):
    """Decode-path EP with 2-D-sharded expert weights (no weight
    regather). x is replicated into the region (tokens are tiny)."""
    mo = cfg.moe
    fsdp = dp_axes[-1] if dp_axes else None
    wspec_in = P(model_axis, fsdp, None)          # (E, d, ff)
    wspec_out = P(model_axis, None, fsdp)         # (E, ff, d)
    espec = {"router": P(), "wi": wspec_in, "wg": wspec_in,
             "wo": wspec_out}
    if mo.n_shared:
        espec["shared"] = jax.tree.map(lambda _: P(), params["shared"])
    fn = partial(_moe_gathered2d_local, cfg=cfg, model_axis=model_axis,
                 fsdp_axis=fsdp)
    out, aux = jax.shard_map(
        lambda p, xx: fn(p, xx),
        mesh=mesh,
        in_specs=(espec, P(None, None, None)),
        out_specs=(P(None, None, None), P()),
        check_vma=False,
    )(params, x)
    return out, aux


def moe_apply(params, x, cfg, *, mesh=None, dp_axes=("data",),
              model_axis="model", path="auto"):
    """Step-4 dispatch: a2a (SpDMM, train/prefill), gathered (SpDMM,
    decode), or dense (DDMM fallback / small-E TP)."""
    mo = cfg.moe
    ep_ok = mesh is not None and mo.n_experts % mesh.shape[model_axis] == 0
    if path == "auto":
        path = "dense"
        if mo.impl == "a2a" and ep_ok:
            # a2a needs S divisible by the model axis; decode (S==1) uses
            # the gathered path instead.
            path = "a2a" if x.shape[1] % mesh.shape[model_axis] == 0 \
                else "gathered"
    if path == "a2a" and ep_ok:
        return moe_a2a(params, x, cfg, mesh=mesh, dp_axes=dp_axes,
                       model_axis=model_axis)
    if path == "gathered" and ep_ok:
        import os as _os
        fsdp = dp_axes[-1] if dp_axes else None
        if fsdp and cfg.d_model % mesh.shape[fsdp] == 0 \
                and not _os.environ.get("REPRO_MOE_1D"):
            return moe_gathered2d(params, x, cfg, mesh=mesh,
                                  dp_axes=dp_axes, model_axis=model_axis)
        return moe_gathered(params, x, cfg, mesh=mesh, dp_axes=dp_axes,
                            model_axis=model_axis)
    return moe_dense(params, x, cfg)
