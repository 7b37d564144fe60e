"""Mixture-of-Experts FFN (GShard-style capacity dispatch, sort-based ranks).

Dispatch avoids materializing the (T, k, E) one-hot: expert ranks are computed
with a sort over the T*k assignment list, tokens are scattered into a dense
(E, C, d) buffer (overflow dropped), experts run as a single batched einsum
(expert dim shardable over the "model" axis = expert parallelism), and results
are combined with a weighted scatter-add. Compiled FLOPs ~= activated FLOPs
times the capacity factor, so roofline numbers stay honest.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, dtype_of


def init_moe(key, cfg):
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_expert, m.n_experts
    dt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 7)
    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "wi": dense_init(ks[1], (e, d, f), dt),
        "wg": dense_init(ks[2], (e, d, f), dt),
        "wo": dense_init(ks[3], (e, f, d), dt),
    }
    if m.n_shared_experts:
        fs = f * m.n_shared_experts
        p["shared_wi"] = dense_init(ks[4], (d, fs), dt)
        p["shared_wg"] = dense_init(ks[5], (d, fs), dt)
        p["shared_wo"] = dense_init(ks[6], (fs, d), dt)
    return p


def apply_moe(p, x, cfg):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    Under a production mesh (meshctx set) with E % model_size == 0, uses the
    explicit expert-parallel shard_map path (local per-data-shard dispatch,
    FSDP weight all-gather, one psum per layer). Otherwise the pure-GSPMD
    global-dispatch path below (correct everywhere, used by CPU tests)."""
    from repro.models import meshctx
    if meshctx.ep_available(cfg):
        mesh = meshctx.get_mesh()
        dp_size = 1
        for a in meshctx.dp_axes():
            dp_size *= mesh.shape[a]
        tokens = x.shape[0] * x.shape[1]
        if (cfg.fsdp and tokens <= 4096
                and x.shape[0] % dp_size == 0
                and cfg.d_model % mesh.shape["data"] == 0):
            # decode regime: gathering FSDP expert weights per token costs
            # ~params bytes; gather the (tiny) token set instead and contract
            # over the local d-slice of the stationary weights.
            return apply_moe_ep_decode(p, x, cfg, mesh)
        if x.shape[0] % dp_size == 0:  # shard_map needs batch divisibility
            return apply_moe_ep(p, x, cfg, mesh)
    return _apply_moe_global(p, x, cfg)


def _apply_moe_global(p, x, cfg):
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    k, e = m.top_k, m.n_experts
    cap = max(1, math.ceil(t * k / e * m.capacity_factor))

    xf = x.reshape(t, d)
    logits = (xf.astype(jnp.float32) @ p["router"])            # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                     # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # sort-based rank within expert
    e_flat = top_e.reshape(t * k)
    order = jnp.argsort(e_flat)                                # stable
    e_sorted = e_flat[order]
    counts = jnp.zeros((e,), jnp.int32).at[e_flat].add(1)
    offsets = jnp.cumsum(counts) - counts                      # exclusive
    rank_sorted = jnp.arange(t * k, dtype=jnp.int32) - offsets[e_sorted]

    tok_sorted = (order // k).astype(jnp.int32)
    w_sorted = top_p.reshape(t * k)[order]

    # dispatch: (E, C, d) buffer; overflow (rank >= cap) dropped
    buf = jnp.zeros((e, cap, d), x.dtype)
    buf = buf.at[e_sorted, rank_sorted].set(
        xf[tok_sorted].astype(x.dtype), mode="drop")

    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"])
    g = jnp.einsum("ecd,edf->ecf", buf, p["wg"])
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, p["wo"])

    # combine: gather each assignment's expert output, weighted scatter-add
    gathered = y.at[e_sorted, rank_sorted].get(
        mode="fill", fill_value=0.0)                           # (T*k, d)
    out = jnp.zeros((t, d), jnp.float32).at[tok_sorted].add(
        gathered.astype(jnp.float32) * w_sorted[:, None])
    out = out.astype(x.dtype)

    if m.n_shared_experts:
        sh = (jax.nn.silu(xf @ p["shared_wg"]) * (xf @ p["shared_wi"])) @ p["shared_wo"]
        out = out + sh

    # load-balance aux loss (Switch-style): E * sum_e f_e * p_bar_e
    f_e = counts.astype(jnp.float32) / (t * k)
    p_bar = probs.mean(axis=0)
    aux = e * jnp.sum(f_e * p_bar) * m.router_aux_weight
    return out.reshape(b, s, d), aux


# ------------------------------------------------------------------ EP path
def apply_moe_ep(p, x, cfg, mesh):
    """Explicit expert parallelism via shard_map.

    Tokens stay sharded over the data axes; each data shard dispatches
    LOCALLY (no global sort => no global collectives); expert weights are
    sharded E over 'model' (+ FSDP dim over 'data', all-gathered just-in-time
    and re-sharded in the backward pass); each model shard computes only its
    own experts and contributes a partial token-output, combined with one
    psum over 'model' per layer — the same volume as a dense TP layer's
    activation all-reduce.
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    b, s, d = x.shape
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    n_model = mesh.shape["model"]
    e_loc = m.n_experts // n_model
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    t_loc = (b * s) // dp_size
    k = m.top_k
    # floor of 4 keeps tiny decode batches from starving experts
    cap = max(4, math.ceil(t_loc * k / m.n_experts * m.capacity_factor))

    def body(xs, router, wi, wg, wo):
        bl = xs.shape[0]
        if cfg.fsdp:
            wi = jax.lax.all_gather(wi, "data", axis=1, tiled=True)
            wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wo = jax.lax.all_gather(wo, "data", axis=2, tiled=True)
        xf = xs.reshape(-1, d)
        t = xf.shape[0]
        logits = xf.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

        e_flat = top_e.reshape(t * k)
        order = jnp.argsort(e_flat)
        e_sorted = e_flat[order]
        counts = jnp.zeros((m.n_experts,), jnp.int32).at[e_flat].add(1)
        offsets = jnp.cumsum(counts) - counts
        rank_sorted = jnp.arange(t * k, dtype=jnp.int32) - offsets[e_sorted]
        tok_sorted = (order // k).astype(jnp.int32)
        w_sorted = top_p.reshape(t * k)[order]

        lo = jax.lax.axis_index("model").astype(jnp.int32) * e_loc
        el = e_sorted - lo
        mine = (el >= 0) & (el < e_loc) & (rank_sorted < cap)
        el_s = jnp.where(mine, el, e_loc)            # positive OOB sentinel
        rk_s = jnp.where(mine, rank_sorted, cap)

        buf = jnp.zeros((e_loc, cap, d), xs.dtype)
        buf = buf.at[el_s, rk_s].set(xf[tok_sorted].astype(xs.dtype),
                                     mode="drop")
        h = jnp.einsum("ecd,edf->ecf", buf, wi)
        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wo)
        gathered = y.at[el_s, rk_s].get(mode="fill", fill_value=0.0)
        out = jnp.zeros((t, d), jnp.float32).at[tok_sorted].add(
            gathered.astype(jnp.float32) * w_sorted[:, None])
        out = jax.lax.psum(out.astype(xs.dtype), "model")

        f_e = counts.astype(jnp.float32) / (t * k)
        p_bar = probs.mean(axis=0)
        aux = m.n_experts * jnp.sum(f_e * p_bar) * m.router_aux_weight
        aux = jax.lax.pmean(aux, dp)
        return out.reshape(bl, s, d), aux

    wspec_i = P("model", "data" if cfg.fsdp else None, None)
    wspec_o = P("model", None, "data" if cfg.fsdp else None)
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None), wspec_i, wspec_i,
                  wspec_o),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["wi"], p["wg"], p["wo"])

    if m.n_shared_experts:
        xf = x.reshape(-1, d)
        sh = (jax.nn.silu(xf @ p["shared_wg"]) * (xf @ p["shared_wi"])) \
            @ p["shared_wo"]
        out = out + sh.reshape(b, s, d)
    return out, aux


def apply_moe_ep_decode(p, x, cfg, mesh):
    """Decode-regime expert parallelism: weights stay fully sharded
    (E over 'model', d over 'data'); the tiny token set is all-gathered to
    every device, each device contracts over its LOCAL d-slice of its local
    experts, and partials are psum'd. Collective volume is O(tokens*d), not
    O(params) — the FSDP-gather path costs ~params bytes per step, which at
    one token per sequence is catastrophic (see EXPERIMENTS.md §Perf)."""
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    b, s, d = x.shape
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    n_model = mesh.shape["model"]
    n_data = mesh.shape["data"]
    e_loc = m.n_experts // n_model
    d_loc = d // n_data
    k = m.top_k
    t_all = b * s
    cap = max(4, math.ceil(t_all * k / m.n_experts * m.capacity_factor))

    def body(xs, router, wi, wg, wo):
        # xs: (b_local, s, d) -> gather ALL tokens (tiny at decode)
        xall = jax.lax.all_gather(xs, dp, axis=0, tiled=True)
        xf = xall.reshape(-1, d)
        t = xf.shape[0]
        logits = xf.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

        e_flat = top_e.reshape(t * k)
        order = jnp.argsort(e_flat)
        e_sorted = e_flat[order]
        counts = jnp.zeros((m.n_experts,), jnp.int32).at[e_flat].add(1)
        offsets = jnp.cumsum(counts) - counts
        rank_sorted = jnp.arange(t * k, dtype=jnp.int32) - offsets[e_sorted]
        tok_sorted = (order // k).astype(jnp.int32)
        w_sorted = top_p.reshape(t * k)[order]

        lo = jax.lax.axis_index("model").astype(jnp.int32) * e_loc
        el = e_sorted - lo
        mine = (el >= 0) & (el < e_loc) & (rank_sorted < cap)
        el_s = jnp.where(mine, el, e_loc)
        rk_s = jnp.where(mine, rank_sorted, cap)

        buf = jnp.zeros((e_loc, cap, d), xs.dtype)
        buf = buf.at[el_s, rk_s].set(xf[tok_sorted].astype(xs.dtype),
                                     mode="drop")
        # contract over the LOCAL d-slice; psum partials over 'data'
        di = jax.lax.axis_index("data").astype(jnp.int32) * d_loc
        buf_sl = jax.lax.dynamic_slice_in_dim(buf, di, d_loc, axis=2)
        h = jax.lax.psum(
            jnp.einsum("ecd,edf->ecf", buf_sl, wi), "data")
        g = jax.lax.psum(
            jnp.einsum("ecd,edf->ecf", buf_sl, wg), "data")
        y_part = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wo)
        y = jax.lax.all_gather(y_part, "data", axis=2, tiled=True)

        gathered = y.at[el_s, rk_s].get(mode="fill", fill_value=0.0)
        out = jnp.zeros((t, d), jnp.float32).at[tok_sorted].add(
            gathered.astype(jnp.float32) * w_sorted[:, None])
        out = jax.lax.psum(out.astype(xs.dtype), "model")
        # slice back this shard's tokens
        bi = jax.lax.axis_index(dp[0]) if len(dp) == 1 else (
            jax.lax.axis_index("pod") * mesh.shape["data"]
            + jax.lax.axis_index("data"))
        bl = xs.shape[0]
        out_local = jax.lax.dynamic_slice_in_dim(
            out.reshape(xall.shape[0], s, d), bi.astype(jnp.int32) * bl, bl,
            axis=0)

        f_e = counts.astype(jnp.float32) / (t * k)
        p_bar = probs.mean(axis=0)
        aux = m.n_experts * jnp.sum(f_e * p_bar) * m.router_aux_weight
        return out_local, aux

    wspec_i = P("model", "data", None)
    wspec_o = P("model", None, "data")
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None), wspec_i, wspec_i,
                  wspec_o),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["wi"], p["wg"], p["wo"])

    if m.n_shared_experts:
        xf = x.reshape(-1, d)
        sh = (jax.nn.silu(xf @ p["shared_wg"]) * (xf @ p["shared_wi"])) \
            @ p["shared_wo"]
        out = out + sh.reshape(b, s, d)
    return out, aux
