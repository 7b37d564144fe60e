"""GSPMD sharding rules: param pytree path+shape -> PartitionSpec.

Baseline scheme:
  * tensor parallel over "model": attention head / d_ff / expert / vocab dims
  * data parallel over ("pod","data"): batch dims of activations and caches
  * fsdp configs additionally shard the non-TP param dim over "data"

Dims are only sharded when divisible by the axis size (uneven GSPMD padding
is avoided in the baseline; hillclimbs may relax this).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def dp_axes(mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _axsize(mesh, axis):
    if isinstance(axis, tuple):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def _ok(mesh, dim, axis):
    return axis is not None and dim % _axsize(mesh, axis) == 0


def _guard(mesh, shape, spec):
    """Drop axes that don't divide their dim."""
    out = []
    for dim, ax in zip(shape, spec):
        out.append(ax if _ok(mesh, dim, ax) else None)
    return P(*out)


def param_pspec(path, leaf, cfg, mesh):
    names = [p.key for p in path if hasattr(p, "key")]
    name = names[-1] if names else ""
    stacked = "blocks" in names  # scan-stacked: leading group dim unsharded
    fsdp = "data" if cfg.fsdp else None
    shape = leaf.shape[1:] if stacked else leaf.shape

    def out(*spec):
        spec = _guard(mesh, shape, spec)
        if stacked:
            spec = P(None, *spec)
        return spec

    nd = len(shape)
    if name == "embed":
        return out("model", fsdp)
    if name == "lm_head":
        return out(fsdp, "model")
    if name in ("wi", "wg", "wo") and nd == 3:          # MoE experts (E, ., .)
        if name == "wo":
            return out("model", None, fsdp)
        return out("model", fsdp, None)
    if name in ("wq", "wk", "wv", "wi", "wg", "wx", "wz", "wdt", "wgate",
                "shared_wi", "shared_wg") and nd == 2:
        return out(fsdp, "model")
    if name in ("wq_a", "wkv_a") and nd == 2:    # MLA down-projections
        return out(fsdp, None)
    if name in ("wq_b", "wkv_b") and nd == 2:    # MLA up-projections (heads)
        return out(None, "model")
    if name in ("wbc", "conv_bc") and nd == 2:   # head-shared B/C: replicate
        return out(None, None)
    if name in ("wo", "out_proj", "out", "shared_wo") and nd == 2:
        return out("model", fsdp)
    if name in ("wa",) and nd == 2:                     # RG-LRU gates (D, D)
        return out(None, "model")
    if name == "router":
        return out(fsdp, None)
    if name in ("conv_w", "conv_x"):
        return out(None, "model")
    if name in ("bq", "bk", "bv", "conv_b", "conv_x_b") and nd == 1:
        return out("model")
    return out(*([None] * nd))


def params_pspecs(mesh, params, cfg):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_pspec(path, leaf, cfg, mesh), params)


def params_shardings(mesh, params, cfg):
    def f(path, leaf):
        return NamedSharding(mesh, param_pspec(path, leaf, cfg, mesh))
    return jax.tree_util.tree_map_with_path(f, params)


def wrap(mesh, pspec_tree):
    """PartitionSpec tree -> NamedSharding tree."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspec_tree,
        is_leaf=lambda x: isinstance(x, P))


def bytes_per_device(struct_tree, sharding_tree):
    """Per-device bytes of a ShapeDtypeStruct tree under given shardings."""
    total = 0
    for leaf, sh in zip(jax.tree_util.tree_leaves(struct_tree),
                        jax.tree_util.tree_leaves(
                            sharding_tree,
                            is_leaf=lambda x: isinstance(x, NamedSharding))):
        shard_shape = sh.shard_shape(leaf.shape)
        n = 1
        for d in shard_shape:
            n *= d
        total += n * leaf.dtype.itemsize
    return total


def batch_pspec(mesh):
    return P(dp_axes(mesh))


def batch_shardings(mesh, batch_tree):
    """Shard the leading (batch) dim of every leaf over the dp axes."""
    dp = dp_axes(mesh)

    def f(leaf):
        spec = [dp] + [None] * (len(leaf.shape) - 1)
        if leaf.shape[0] % _axsize(mesh, dp) != 0:
            spec[0] = None
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map(f, batch_tree)


def cache_shardings(mesh, cache_tree, cfg):
    """Caches: batch over dp; kv-head / state-head dims over model when they
    divide. Leaves are stacked (n_groups, B, ...) for scanned blocks — detect
    by path containing 'blocks'."""
    dp = dp_axes(mesh)

    def f(path, leaf):
        names = [p.key for p in path if hasattr(p, "key")]
        stacked = "blocks" in [str(n) for n in names] or any(
            getattr(p, "idx", None) is not None and "blocks" in str(path)
            for p in path)
        # robust stacked detection: blocks entries come as
        # ('blocks', idx, leafname); tail as ('tail', idx, leafname)
        stacked = "blocks" in str(path)
        shape = leaf.shape[1:] if stacked else leaf.shape
        name = names[-1] if names else ""
        spec = [None] * len(shape)
        if len(shape) >= 1 and shape[0] % _axsize(mesh, dp) == 0:
            spec[0] = dp
        if name in ("k", "v", "ck", "cv") and len(shape) == 4:
            # sequence-parallel KV cache: shard the LENGTH dim over 'model'
            # (kv-head counts rarely divide the TP degree; cache length
            # always does). Decode attention merges per-shard partials —
            # see attention._flash_decode.
            if shape[1] % mesh.shape["model"] == 0:
                spec[1] = "model"
            elif shape[2] % mesh.shape["model"] == 0:
                spec[2] = "model"
        if name == "pos" and len(shape) == 2:
            if shape[1] % mesh.shape["model"] == 0:
                spec[1] = "model"
        if name in ("k_scale", "v_scale") and len(shape) == 3:
            if shape[1] % mesh.shape["model"] == 0:
                spec[1] = "model"
        if name == "h" and len(shape) == 4:             # SSM (B, H, P, N)
            if shape[1] % mesh.shape["model"] == 0:
                spec[1] = "model"
        if name in ("conv", "conv_x") and len(shape) == 3:
            if shape[2] % mesh.shape["model"] == 0:
                spec[2] = "model"
        if name == "h" and len(shape) == 2:             # RG-LRU (B, D)
            if shape[1] % mesh.shape["model"] == 0:
                spec[1] = "model"
        if stacked:
            spec = [None] + spec
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map_with_path(f, cache_tree)
