"""Residual block variants and their per-layer cache handling.

Block types:
  dense  attn + MLP                 moe    attn + MoE-FFN
  lattn  local-window attn + MLP    rec    RG-LRU + MLP (Griffin)
  mamba2 SSD mixer                  enc    bidirectional attn + MLP
  xattn  gated cross-attn + MLP     decx   self-attn + cross-attn + MLP
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models.layers import (apply_mlp, apply_norm, dtype_of, init_mlp,
                                 init_norm)
from repro.models.moe import apply_moe, init_moe
from repro.models.rglru import apply_rglru, decode_rglru, init_rglru
from repro.models.ssm import apply_mamba, decode_mamba, init_mamba

ATTN_TYPES = ("dense", "moe", "lattn", "enc", "decx")


def init_block(key, cfg, btype):
    ks = jax.random.split(key, 4)
    if btype == "mamba2":
        return {"ln1": init_norm(cfg), "mixer": init_mamba(ks[0], cfg)}
    if btype == "rec":
        return {"ln1": init_norm(cfg), "mixer": init_rglru(ks[0], cfg),
                "ln2": init_norm(cfg), "mlp": init_mlp(ks[1], cfg)}
    if cfg.mla and btype in ("dense", "moe"):
        ffn = ({"moe": init_moe(ks[1], cfg)} if btype == "moe"
               else {"mlp": init_mlp(ks[1], cfg)})
        return {"ln1": init_norm(cfg), "attn": attn_lib.init_mla(ks[0], cfg),
                "ln2": init_norm(cfg), **ffn}
    if btype in ("dense", "lattn", "enc"):
        return {"ln1": init_norm(cfg), "attn": attn_lib.init_attn(ks[0], cfg),
                "ln2": init_norm(cfg), "mlp": init_mlp(ks[1], cfg)}
    if btype == "moe":
        return {"ln1": init_norm(cfg), "attn": attn_lib.init_attn(ks[0], cfg),
                "ln2": init_norm(cfg), "moe": init_moe(ks[1], cfg)}
    if btype == "xattn":
        return {"ln1": init_norm(cfg),
                "xattn": attn_lib.init_attn(ks[0], cfg, cross=True),
                "ln2": init_norm(cfg), "mlp": init_mlp(ks[1], cfg)}
    if btype == "decx":
        p = {"ln1": init_norm(cfg), "attn": attn_lib.init_attn(ks[0], cfg),
             "lnx": init_norm(cfg),
             "xattn": attn_lib.init_attn(ks[1], cfg, cross=True),
             "ln2": init_norm(cfg), "mlp": init_mlp(ks[2], cfg)}
        del p["xattn"]["gate"]  # enc-dec cross-attn is ungated
        return p
    raise ValueError(f"unknown block type {btype}")


def _ffn(p, x, cfg):
    """Second residual half; returns (delta, aux_loss)."""
    if "moe" in p:
        h = apply_norm(p["ln2"], x, cfg)
        out, aux = apply_moe(p["moe"], h, cfg)
        return out, aux
    h = apply_norm(p["ln2"], x, cfg)
    return apply_mlp(p["mlp"], h, cfg), 0.0


def apply_block(p, x, cfg, btype, *, positions, mode, context=None,
                cache=None, idx=None, attn_len=0):
    """Apply one residual block.

    mode: "train" (no cache output), "prefill" (build cache entry),
    "decode" (consume+update cache entry).
    Returns (x, cache_entry, aux_loss); cache_entry is () in train mode.
    """
    from repro.models.cache import (pack_full_kv,  # local import (cycle-free)
                                    pack_latent)

    aux = 0.0
    window = cfg.window if btype == "lattn" else 0

    if btype == "mamba2":
        h = apply_norm(p["ln1"], x, cfg)
        if mode == "decode":
            out, entry = decode_mamba(p["mixer"], h, cfg, cache)
        else:
            out, entry = apply_mamba(p["mixer"], h, cfg)
        x = x + out
        return x, (() if mode == "train" else entry), aux

    if btype == "rec":
        h = apply_norm(p["ln1"], x, cfg)
        if mode == "decode":
            out, entry = decode_rglru(p["mixer"], h, cfg, cache)
        else:
            out, entry = apply_rglru(p["mixer"], h, cfg)
        x = x + out
        d, aux = _ffn(p, x, cfg)
        return x + d, (() if mode == "train" else entry), aux

    if btype == "xattn":
        h = apply_norm(p["ln1"], x, cfg)
        if mode == "decode":
            out, _ = attn_lib.cross_attention(
                p["xattn"], h, cfg, kv=(cache["ck"], cache["cv"]))
            entry = cache
        else:
            out, (ck, cv) = attn_lib.cross_attention(
                p["xattn"], h, cfg, context=context)
            entry = () if mode == "train" else {"ck": ck, "cv": cv}
        x = x + out
        d, aux = _ffn(p, x, cfg)
        return x + d, entry, aux

    if cfg.mla and btype in ("dense", "moe"):
        h = apply_norm(p["ln1"], x, cfg)
        if mode == "decode":
            slot = jax.lax.rem(idx, cache["ckv"].shape[1])
            out, entry = attn_lib.mla_decode(p["attn"], h, cfg, positions,
                                             cache, slot)
        else:
            out, (c_kv, k_rope) = attn_lib.mla_attention(p["attn"], h, cfg,
                                                         positions)
            entry = () if mode == "train" else pack_latent(
                c_kv, k_rope, positions, attn_len,
                dtype_of(cfg.compute_dtype))
        x = x + out
        d, aux = _ffn(p, x, cfg)
        return x + d, entry, aux

    # attention blocks: dense / moe / lattn / enc / decx
    h = apply_norm(p["ln1"], x, cfg)
    causal = btype != "enc"
    if mode == "decode":
        lc = cache["k"].shape[1]
        slot = jax.lax.rem(idx, lc)
        pos_buf = jax.lax.dynamic_update_slice(
            cache["pos"], positions.astype(jnp.int32), (0, slot))
        out, kv = attn_lib.self_attention(
            p["attn"], h, cfg, positions, causal=True, window=window,
            kv_cache=cache, cache_slot=slot, cache_positions=pos_buf)
        entry = dict(kv, pos=pos_buf)
    else:
        out, (k, v) = attn_lib.self_attention(
            p["attn"], h, cfg, positions, causal=causal, window=window)
        if mode == "train" or btype == "enc":
            entry = ()
        else:
            entry = pack_full_kv(k, v, positions, attn_len, window=window,
                                 kv_bits=cfg.kv_quant_bits)
    x = x + out

    if btype == "decx":
        hx = apply_norm(p["lnx"], x, cfg)
        if mode == "decode":
            xout, _ = attn_lib.cross_attention(
                p["xattn"], hx, cfg, kv=(cache["ck"], cache["cv"]))
        else:
            xout, (ck, cv) = attn_lib.cross_attention(
                p["xattn"], hx, cfg, context=context)
            if entry != ():
                entry = dict(entry, ck=ck, cv=cv)
        x = x + xout
        if mode == "decode":
            entry = dict(entry, ck=cache["ck"], cv=cache["cv"])

    d, aux = _ffn(p, x, cfg)
    return x + d, entry, aux
