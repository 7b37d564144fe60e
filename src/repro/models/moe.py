"""Mixture-of-Experts FFN: GShard-style capacity dispatch with sort-based
ranks, and a dropless dispatch for the DeepSeek-V3 sigmoid router.

Dispatch avoids materializing the (T, k, E) one-hot: expert ranks are computed
with a sort over the T*k assignment list, tokens are scattered into a dense
(E, C, d) buffer (overflow dropped), experts run as a single batched einsum
(expert dim shardable over the "model" axis = expert parallelism), and results
are combined with a weighted scatter-add. Compiled FLOPs ~= activated FLOPs
times the capacity factor, so roofline numbers stay honest.

Dropless dispatch (``MoEConfig.dropless``): the T*k token-slots are sorted
by expert, one grouped matmul per projection runs over the group sizes
(``kernels.ops.grouped_matmul``), and the slots are unsorted and summed
with their weights. Every token reaches all k of its experts however
uneven the routing. Under a mesh it runs expert-parallel in the same two
regimes as the capacity dispatch (``_routed_ep``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.layers import dense_init, dtype_of, mm


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
    if m.dropless:
        p["router_bias"] = jnp.zeros((e,), jnp.float32)
    return p


def route(p, xf, m):
    """DeepSeek-V3's ``noaux_tc`` router with one group: (expert ids
    (T, k), weights (T, k), scores (T, E)) of tokens ``xf`` (T, d), all
    float32. The experts are the top k of ``sigmoid(xf W) + router_bias``;
    the bias chooses them and does not weight them: the k sigmoid scores
    are normalised to sum to 1 and scaled by ``routed_scaling``. The
    router's product runs at the highest precision, so near-ties do not
    flip on rounding."""
    z = jnp.dot(xf.astype(jnp.float32), p["router"],
                precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(z)
    _, idx = jax.lax.top_k(scores + p["router_bias"], m.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-20) * m.routed_scaling
    return idx, w, scores


def _swiglu_experts(wg, wi, wo):
    """Grouped SwiGLU of rows sorted by expert: (rows, sizes) -> (rows, d)
    float32, over the experts of ``wg``/``wi``/``wo``."""
    def experts(rows, sizes):
        g = ops.grouped_matmul(rows, wg, sizes)
        h = jax.nn.silu(g) * ops.grouped_matmul(rows, wi, sizes)
        return ops.grouped_matmul(h.astype(wo.dtype), wo, sizes)
    return experts


def _routed(xf, idx, w, n_local, experts, lo=None):
    """(T, d) float32: the weighted sum of each token's k expert outputs.
    The T*k slots are sorted by expert, ``experts`` runs the grouped
    matmuls over the ``n_local`` group sizes, and the slots are unsorted
    and summed with their weights ``w``. With ``lo`` the experts are
    ``lo .. lo + n_local - 1`` of a larger set: the slots of the others
    sort after every group, are counted last in the sizes (the grouped
    matmul's form for rows past its groups), come out of it as zero and
    add nothing."""
    t, k = idx.shape
    d = xf.shape[-1]
    with jax.named_scope("moe_route"):
        flat = idx.reshape(t * k)
        counts = n_local
        if lo is not None:
            flat = flat - lo
            flat = jnp.where((flat >= 0) & (flat < n_local), flat, n_local)
            counts += 1
        order = jnp.argsort(flat)
        sizes = jnp.bincount(flat, length=counts).astype(jnp.int32)
        rows = xf[order // k]
    with jax.named_scope("moe_experts"):
        y = experts(rows, sizes)
    with jax.named_scope("moe_route"):
        slot_of = jnp.zeros((t * k,), jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32))
        # the weighted sum is elementwise: a product here would round to
        # the MXU's input precision
        return jnp.sum(y[slot_of].reshape(t, k, d) * w[..., None], axis=1)


def _balance_sums(idx, scores, e):
    """(slots per expert (E,), summed normalised scores (E,)) of a token
    set: the sums the load-balance loss is made of."""
    counts = jnp.bincount(idx.reshape(-1), length=e).astype(jnp.float32)
    return counts, (scores / scores.sum(-1, keepdims=True)).sum(axis=0)


def apply_moe_dropless(p, x, cfg, mesh=None, decode=False):
    """x: (B, S, d) -> (out (B, S, d), aux_loss). Scopes: ``moe_route``
    (scores, top-k, sort, unsort and the weighted sum), ``moe_experts``
    (the routed grouped matmuls) and ``moe_shared``. With ``mesh`` the
    router and the routed experts run expert-parallel (``_routed_ep``)."""
    m = cfg.moe
    b, s, d = x.shape
    t, k, e = b * s, m.top_k, m.n_experts
    xf = x.reshape(t, d)
    if mesh is None:
        with jax.named_scope("moe_route"):
            idx, w, scores = route(p, xf, m)
        out = _routed(xf.astype(p["wi"].dtype), idx, w, e,
                      _swiglu_experts(p["wg"], p["wi"], p["wo"]))
        counts, p_sum = _balance_sums(idx, scores, e)
    else:
        out, counts, p_sum = _routed_ep(p, x, cfg, mesh, decode)
        out = out.reshape(t, d)
    if m.n_shared_experts:
        with jax.named_scope("moe_shared"):
            hs = jax.nn.silu(mm(xf, p["shared_wg"])) * mm(xf, p["shared_wi"])
            out = out + mm(hs, p["shared_wo"])
    aux = e * jnp.sum(counts / (t * k) * p_sum / t) * m.router_aux_weight
    return out.astype(x.dtype).reshape(b, s, d), aux


def apply_moe(p, x, cfg):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    Under a production mesh (meshctx set) with E % model_size == 0, the
    experts run expert-parallel under shard_map: local per-data-shard
    dispatch, FSDP weight all-gather, one psum per layer; or, for a small
    token set under FSDP, the decode regime that gathers the tokens
    instead of the weights. Otherwise the pure-GSPMD global-dispatch path
    below (correct everywhere, used by CPU tests). A dropless layer takes
    ``apply_moe_dropless`` in the same regimes."""
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
            if cfg.moe.dropless:
                return apply_moe_dropless(p, x, cfg, mesh, decode=True)
            return apply_moe_ep_decode(p, x, cfg, mesh)
        if x.shape[0] % dp_size == 0:  # shard_map needs batch divisibility
            if cfg.moe.dropless:
                return apply_moe_dropless(p, x, cfg, mesh)
            return apply_moe_ep(p, x, cfg, mesh)
    if cfg.moe.dropless:
        return apply_moe_dropless(p, x, cfg)
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


def _routed_ep(p, x, cfg, mesh, decode):
    """The router and ``_routed`` expert-parallel, for the dropless layer:
    ((B, S, d) float32, the ``_balance_sums`` of all tokens). Each 'model'
    shard runs the grouped matmul of its own E/model_size experts and the
    partial sums meet in one psum over 'model', as in ``apply_moe_ep``.
    Without ``decode`` each data shard routes and sorts its own tokens,
    with the FSDP weights gathered over 'data'; with ``decode`` every
    shard takes the whole (small) token set and contracts over its local
    d-slice of the stationary weights, as in ``apply_moe_ep_decode``.
    ``_routed`` counts the other shards' slots last in the group sizes, so
    that the grouped matmul returns zero for them: its (g,) form must
    cover every row, and the psums would add rows it left undefined."""
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    b, s, d = x.shape
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    e_loc = m.n_experts // mesh.shape["model"]
    d_loc = d // mesh.shape["data"]
    wdt = p["wi"].dtype

    def body(xs, router, bias, wi, wg, wo):
        lo = jax.lax.axis_index("model").astype(jnp.int32) * e_loc
        xf = xs.reshape(-1, d)
        if decode:
            xf = jax.lax.all_gather(xf, dp, axis=0, tiled=True)
            di = jax.lax.axis_index("data").astype(jnp.int32) * d_loc

            def experts(rows, sizes):
                rs = jax.lax.dynamic_slice_in_dim(rows, di, d_loc, axis=1)
                g = jax.lax.psum(ops.grouped_matmul(rs, wg, sizes), "data")
                h = jax.nn.silu(g) * jax.lax.psum(
                    ops.grouped_matmul(rs, wi, sizes), "data")
                y = ops.grouped_matmul(h.astype(wdt), wo, sizes)
                return jax.lax.all_gather(y, "data", axis=1, tiled=True)
        else:
            if cfg.fsdp:
                wi = jax.lax.all_gather(wi, "data", axis=1, tiled=True)
                wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
                wo = jax.lax.all_gather(wo, "data", axis=2, tiled=True)
            experts = _swiglu_experts(wg, wi, wo)
        with jax.named_scope("moe_route"):
            idx, w, scores = route({"router": router, "router_bias": bias},
                                   xf, m)
        out = jax.lax.psum(_routed(xf.astype(wdt), idx, w, e_loc, experts,
                                   lo), "model")
        counts, p_sum = _balance_sums(idx, scores, m.n_experts)
        if not decode:
            return out.reshape(xs.shape), jax.lax.psum(
                counts, dp), jax.lax.psum(p_sum, dp)
        # this shard's tokens
        bi = jax.lax.axis_index(dp[0]) if len(dp) == 1 else (
            jax.lax.axis_index("pod") * mesh.shape["data"]
            + jax.lax.axis_index("data"))
        bl = xs.shape[0]
        return jax.lax.dynamic_slice_in_dim(
            out.reshape(-1, s, d), bi.astype(jnp.int32) * bl, bl,
            axis=0), counts, p_sum

    fsdp = "data" if cfg.fsdp else None
    wspec_i = P("model", fsdp, None)
    wspec_o = P("model", None, fsdp)
    tok = P(dp, None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(tok, P(None, None), P(None), wspec_i, wspec_i, wspec_o),
        out_specs=(tok, P(), P()), check_vma=False,
    )(x, p["router"], p["router_bias"], p["wi"], p["wg"], p["wo"])
