"""Serving caches: full / ring-buffer KV caches, SSM and RG-LRU states.

The cache pytree mirrors the parameter layout (per-pattern-position stacks
over scan groups + unstacked tail) so the same lax.scan drives both. Slot
semantics: an entry with absolute position p lives at slot p % cache_len;
``pos`` maps slot -> absolute position (-1 = empty), which the flash-attention
mask consumes directly, making full and sliding-window caches uniform.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dtype_of
from repro.models import ssm as ssm_lib


def quantize_kv(x, bits):
    """Symmetric per-(token, kv-head) int8 quantization of k or v
    (B, S, Hkv, D) -> (codes int8, scale (B, S, Hkv) f32). Paper Eq. 1
    applied to the serving cache."""
    levels = (1 << (bits - 1)) - 1
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / levels
    scale = jnp.maximum(scale, 1e-8)
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                     -levels, levels).astype(jnp.int8)
    return codes, scale


def dequantize_kv(codes, scale, dtype):
    return (codes.astype(jnp.float32) * scale[..., None]).astype(dtype)


def pack_full_kv(k, v, positions, cache_len, window=0, kv_bits=0):
    """Build a decode cache entry from full-sequence k/v (prefill).

    k, v: (B, S, Hkv, D); positions: (B, S). cache_len: allocated length
    (window if window>0). Entries beyond capacity keep only the most recent.
    kv_bits > 0 stores int8 codes + per-(slot, head) scales.
    """
    lc = window if window else cache_len
    b, s, hkv, dh = k.shape
    ksc = vsc = None
    if kv_bits:
        k, ksc = quantize_kv(k, kv_bits)
        v, vsc = quantize_kv(v, kv_bits)
    if s >= lc:
        ks, vs, ps = k[:, -lc:], v[:, -lc:], positions[:, -lc:]
        slots = jnp.mod(ps[0], lc)                       # (lc,)
        kb = jnp.zeros((b, lc, hkv, dh), k.dtype).at[:, slots].set(ks)
        vb = jnp.zeros((b, lc, hkv, dh), v.dtype).at[:, slots].set(vs)
        pb = jnp.full((b, lc), -1, jnp.int32).at[:, slots].set(ps)
        if kv_bits:
            ksc = jnp.zeros((b, lc, hkv), jnp.float32).at[:, slots].set(
                ksc[:, -lc:])
            vsc = jnp.zeros((b, lc, hkv), jnp.float32).at[:, slots].set(
                vsc[:, -lc:])
    else:
        kb = jnp.zeros((b, lc, hkv, dh), k.dtype)
        kb = jax.lax.dynamic_update_slice(kb, k, (0, 0, 0, 0))
        vb = jnp.zeros((b, lc, hkv, dh), v.dtype)
        vb = jax.lax.dynamic_update_slice(vb, v, (0, 0, 0, 0))
        pb = jnp.full((b, lc), -1, jnp.int32)
        pb = jax.lax.dynamic_update_slice(pb, positions.astype(jnp.int32), (0, 0))
        if kv_bits:
            ksc = jax.lax.dynamic_update_slice(
                jnp.zeros((b, lc, hkv), jnp.float32), ksc, (0, 0, 0))
            vsc = jax.lax.dynamic_update_slice(
                jnp.zeros((b, lc, hkv), jnp.float32), vsc, (0, 0, 0))
    entry = {"k": kb, "v": vb, "pos": pb}
    if kv_bits:
        entry["k_scale"] = ksc
        entry["v_scale"] = vsc
    return entry


def pack_latent(c_kv, k_rope, positions, cache_len, dtype):
    """An MLA layer's cache entry from prefill: the latent ``c_kv`` (B, S,
    r) and the shared RoPE key ``k_rope`` (B, S, dr) in ``dtype``, in
    slots [0, S) of buffers of ``cache_len`` (>= S), with ``pos``."""
    b, s = positions.shape
    pad = ((0, 0), (0, cache_len - s), (0, 0))
    return {"ckv": jnp.pad(c_kv.astype(dtype), pad),
            "krope": jnp.pad(k_rope.astype(dtype), pad),
            "pos": jnp.pad(positions.astype(jnp.int32),
                           ((0, 0), (0, cache_len - s)),
                           constant_values=-1)}


def entry_shape(cfg, btype, batch, attn_len):
    """Shape/dtype tree (as (shape, dtype) leaves) of one layer's cache."""
    cdt = dtype_of(cfg.compute_dtype)
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    if btype == "mamba2":
        d_inner, h, pdim, n, d_conv = ssm_lib.dims(cfg)
        return {"conv_x": ((batch, d_conv - 1, d_inner), cdt),
                "conv_bc": ((batch, d_conv - 1, 2 * n), cdt),
                "h": ((batch, h, pdim, n), jnp.float32)}
    if btype == "rec":
        d_rnn = cfg.d_model
        return {"conv": ((batch, 3, d_rnn), cdt),
                "h": ((batch, d_rnn), jnp.float32)}
    if btype == "xattn":
        return {"ck": ((batch, cfg.n_aux_tokens, hkv, dh), cdt),
                "cv": ((batch, cfg.n_aux_tokens, hkv, dh), cdt)}
    if cfg.mla and btype in ("dense", "moe"):
        # the latent and the shared RoPE key: kv_lora_rank +
        # qk_rope_head_dim values a token, for all heads at once
        return {"ckv": ((batch, attn_len, cfg.kv_lora_rank), cdt),
                "krope": ((batch, attn_len, cfg.qk_rope_head_dim), cdt),
                "pos": ((batch, attn_len), jnp.int32)}
    lc = cfg.window if btype == "lattn" else attn_len
    kv_dt = jnp.int8 if cfg.kv_quant_bits else cdt
    e = {"k": ((batch, lc, hkv, dh), kv_dt),
         "v": ((batch, lc, hkv, dh), kv_dt),
         "pos": ((batch, lc), jnp.int32)}
    if cfg.kv_quant_bits:
        e["k_scale"] = ((batch, lc, hkv), jnp.float32)
        e["v_scale"] = ((batch, lc, hkv), jnp.float32)
    if btype == "decx":
        nf = cfg.encoder.n_frames
        e["ck"] = ((batch, nf, hkv, dh), cdt)
        e["cv"] = ((batch, nf, hkv, dh), cdt)
    return e


def entry_payload_bits(cfg, btype, batch, ctx_len):
    """Bits to ship one layer's serving-cache state for a `ctx_len`-token
    context: ``entry_shape``'s leaves with sequence axes at the FILLED
    length (min(ctx_len, window) for sliding-window layers — the ring
    buffer never holds more), honoring ``kv_quant_bits`` (int8 codes +
    f32 per-(slot, head) scales; an MLA layer's latent is never
    quantized). SSM/RG-LRU layers carry O(1) state.
    The boundary payload of an LLM-decode split
    (core.split.llm_decode_split_table) sums this over the UE-side
    layers, which is what makes f_bits a function of context length."""
    import numpy as np
    ctx_len = int(ctx_len)
    if ctx_len < 1:
        raise ValueError("ctx_len must be >= 1")
    if btype == "lattn" and cfg.window:
        cfg = cfg.replace(window=min(ctx_len, cfg.window))
    total = 0
    for shape, dtype in entry_shape(cfg, btype, batch, ctx_len).values():
        n = 1
        for s in shape:
            n *= int(s)
        total += n * np.dtype(dtype).itemsize * 8
    return int(total)


def make_cache(cfg, batch, attn_len, leaf_fn=None):
    """Build the full cache pytree. leaf_fn(shape, dtype) -> leaf;
    defaults to zeros (pos leaves get -1)."""
    from repro.models.model import layer_plan

    def default_leaf(shape, dtype, is_pos):
        if is_pos:
            return jnp.full(shape, -1, dtype)
        return jnp.zeros(shape, dtype)

    def build(btype, stack_n=None):
        tree = entry_shape(cfg, btype, batch, attn_len)

        def mk(name, sd):
            shape, dtype = sd
            if stack_n is not None:
                shape = (stack_n,) + tuple(shape)
            if leaf_fn is not None:
                return leaf_fn(shape, dtype)
            return default_leaf(shape, dtype, name == "pos")
        return {name: mk(name, sd) for name, sd in tree.items()}

    lead_types, pattern, n_groups, tail_types = layer_plan(cfg)
    blocks = [build(bt, n_groups) for bt in pattern] if n_groups else []
    return {"lead": tuple(build(bt) for bt in lead_types),
            "blocks": tuple(blocks),
            "tail": tuple(build(bt) for bt in tail_types)}
