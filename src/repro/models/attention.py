"""GQA attention: chunked (flash-style) online-softmax implementation usable
for training, prefill and decode, with causal / local-window / bidirectional
masking and ring-buffer KV caches.

The chunked formulation bounds peak activation memory to O(Sq * chunk) per
head instead of O(Sq * Sk) — required for prefill_32k / train_4k to fit HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.layers import (apply_rope, dense_init, dtype_of, mm,
                                 rms_head_norm)

NEG_INF = -1e30


def init_attn(key, cfg, *, cross: bool = False):
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, hq * dh), dt),
        "wk": dense_init(ks[1], (d, hkv * dh), dt),
        "wv": dense_init(ks[2], (d, hkv * dh), dt),
        "wo": dense_init(ks[3], (hq * dh, d), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * dh,), dt)
        p["bk"] = jnp.zeros((hkv * dh,), dt)
        p["bv"] = jnp.zeros((hkv * dh,), dt)
    if cfg.qk_norm:
        p["q_scale"] = jnp.ones((dh,), dt)
        p["k_scale"] = jnp.ones((dh,), dt)
    if cross:
        p["gate"] = jnp.zeros((), dt)  # tanh-gated cross-attn (VLM)
    return p


def _qkv(p, x, xc, cfg):
    """x: (B,S,d) queries source; xc: kv source (==x for self-attn)."""
    b, s, _ = x.shape
    sk = xc.shape[1]
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = xc @ p["wk"]
    v = xc @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, hq, dh)
    k = k.reshape(b, sk, hkv, dh)
    v = v.reshape(b, sk, hkv, dh)
    if cfg.qk_norm:
        q = rms_head_norm(p["q_scale"], q)
        k = rms_head_norm(p["k_scale"], k)
    return q, k, v


def flash_attention(q, k, v, *, q_positions, k_positions, causal=True,
                    window=0, chunk=1024, q_block=2048, k_scale=None,
                    v_scale=None):
    """Online-softmax attention, chunked over the KV axis and (for long
    queries) blocked over the query axis so peak memory is
    O(q_block * chunk) per head rather than O(Sq * Sk).

    q, k: (B, Sq|Sk, Hq|Hkv, D);  v: (B, Sk, Hkv, Dv);  Hq % Hkv == 0.
    q_positions: (B, Sq) int32;  k_positions: (B, Sk) int32, -1 = invalid slot.
    window > 0 limits attention to k_pos in (q_pos - window, q_pos].
    Returns (B, Sq, Hq, Dv) in q.dtype.
    """
    b, sq, hq, dh = q.shape
    if sq > q_block:
        pad = (-sq) % q_block
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pp = jnp.pad(q_positions, ((0, 0), (0, pad)), constant_values=-1)
        nq = qp.shape[1] // q_block
        qp = qp.reshape(b, nq, q_block, hq, dh).transpose(1, 0, 2, 3, 4)
        pp = pp.reshape(b, nq, q_block).transpose(1, 0, 2)
        out = jax.lax.map(
            lambda xs: _flash_inner(xs[0], k, v, q_positions=xs[1],
                                    k_positions=k_positions, causal=causal,
                                    window=window, chunk=chunk),
            (qp, pp))
        out = out.transpose(1, 0, 2, 3, 4).reshape(
            b, nq * q_block, hq, v.shape[-1])
        return out[:, :sq]
    if sq == 1:
        # decode: no sequential dependency — per-chunk partials in parallel,
        # merged with a log-sum-exp combine. GSPMD keeps the cache sharded
        # over 'model' on the length dim (sequence-parallel flash-decode);
        # the merge is a tiny cross-shard reduction instead of gathering the
        # whole cache. See EXPERIMENTS.md §Perf.
        return _flash_decode(q, k, v, q_positions=q_positions,
                             k_positions=k_positions, causal=causal,
                             window=window, chunk=chunk,
                             k_scale=k_scale, v_scale=v_scale)
    assert k_scale is None, "quantized cache is a decode-path feature"
    return _flash_inner(q, k, v, q_positions=q_positions,
                        k_positions=k_positions, causal=causal,
                        window=window, chunk=chunk)


def _flash_decode(q, k, v, *, q_positions, k_positions, causal, window,
                  chunk, k_scale=None, v_scale=None):
    b, sq, hq, dh = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = dh ** -0.5
    mult_dtype = q.dtype if k_scale is not None else k.dtype
    qf = (q.reshape(b, hkv, g, dh) * jnp.asarray(scale, q.dtype)
          ).astype(mult_dtype)
    chunk = min(chunk, sk)
    pad = (-sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, ((0, 0), (0, pad)),
                              constant_values=-1)
        if k_scale is not None:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0)))
    nc = k.shape[1] // chunk
    kc = k.reshape(b, nc, chunk, hkv, dh)
    vc = v.reshape(b, nc, chunk, hkv, dh)
    pc = k_positions.reshape(b, nc, chunk)
    if k_scale is not None:
        kc = kc.astype(mult_dtype)
        vc = vc.astype(mult_dtype)

    s = jnp.einsum("bhgd,bnchd->bnhgc", qf, kc,
                   preferred_element_type=jnp.float32)   # (B,nc,Hkv,G,C)
    if k_scale is not None:
        ksc = k_scale.reshape(b, nc, chunk, hkv).transpose(0, 1, 3, 2)
        s = s * ksc[:, :, :, None, :]                    # (B,nc,Hkv,1,C)
    valid = pc[:, :, None, None, :] >= 0
    qpos = q_positions[:, 0][:, None, None, None, None]
    if causal:
        valid &= pc[:, :, None, None, :] <= qpos
    if window:
        valid &= pc[:, :, None, None, :] > qpos - window
    s = jnp.where(valid, s, NEG_INF)
    m_c = s.max(axis=-1)                                  # (B,nc,Hkv,G)
    p = jnp.exp(s - m_c[..., None])
    l_c = p.sum(axis=-1)
    if v_scale is not None:
        vsc = v_scale.reshape(b, nc, chunk, hkv).transpose(0, 1, 3, 2)
        p = p * vsc[:, :, :, None, :]
    acc_c = jnp.einsum("bnhgc,bnchd->bnhgd", p.astype(vc.dtype), vc,
                       preferred_element_type=jnp.float32)
    m = m_c.max(axis=1)                                   # (B,Hkv,G)
    w = jnp.exp(m_c - m[:, None])
    l = (l_c * w).sum(axis=1)
    acc = (acc_c * w[..., None]).sum(axis=1)              # (B,Hkv,G,D)
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.reshape(b, 1, hq, dh).astype(q.dtype)


def _flash_inner(q, k, v, *, q_positions, k_positions, causal, window, chunk):
    b, sq, hq, dh = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = dh ** -0.5
    # scores multiply in the cache's storage dtype with f32 MXU accumulation
    # (preferred_element_type) — converting the cache to f32 would let XLA
    # hoist a full-cache f32 copy out of the layer scan (15 GB at 32k) and
    # shard+all-gather it. See EXPERIMENTS.md §Perf iteration 1.
    qf = (q.reshape(b, sq, hkv, g, dh) * jnp.asarray(scale, q.dtype)
          ).astype(k.dtype)

    chunk = min(chunk, sk)
    pad = (-sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, ((0, 0), (0, pad)),
                              constant_values=-1)
    n_chunks = k.shape[1] // chunk
    # keep the cache in its storage dtype; cast per-chunk INSIDE the scan —
    # casting up front materializes an f32 copy of the whole cache (15 GB for
    # a 32k GQA cache), which GSPMD then shards+all-gathers. See §Perf log.
    kc = k.reshape(b, n_chunks, chunk, hkv, dh)
    vc = v.reshape(b, n_chunks, chunk, hkv, v.shape[-1])
    pc = k_positions.reshape(b, n_chunks, chunk)

    def step(carry, xs):
        m, l, acc = carry
        kb, vb, pb = xs  # (B,C,Hkv,D), (B,C,Hkv,D), (B,C)
        s = jnp.einsum("bqhgd,bchd->bhgqc", qf, kb,
                       preferred_element_type=jnp.float32)  # (B,Hkv,G,Sq,C)
        valid = pb[:, None, None, None, :] >= 0
        if causal:
            valid &= pb[:, None, None, None, :] <= q_positions[:, None, None, :, None]
        if window:
            valid &= pb[:, None, None, None, :] > (
                q_positions[:, None, None, :, None] - window)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqc,bchd->bhgqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    dv = v.shape[-1]
    m0 = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4),
         pc.transpose(1, 0, 2)))
    out = acc / jnp.maximum(l, 1e-20)[..., None]          # (B,Hkv,G,Sq,Dv)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, dv)
    return out.astype(q.dtype)


def self_attention(p, x, cfg, positions, *, causal=True, window=0,
                   kv_cache=None, cache_slot=None, cache_positions=None):
    """Self-attention for train/prefill (kv_cache=None) or decode.

    Decode: kv_cache = {"k","v"} each (B, L, Hkv, D); the new token's k/v are
    written at ``cache_slot`` (scalar int32, already modulo cache length);
    cache_positions: (B, L) int32 slot->abs-position map (-1 invalid).
    Returns (out, new_kv) where new_kv is the (k, v) content to cache
    (prefill) or the updated cache dict (decode).
    """
    q, k, v = _qkv(p, x, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    if kv_cache is None:
        out = flash_attention(q, k, v, q_positions=positions,
                              k_positions=positions, causal=causal,
                              window=window, chunk=cfg.attn_chunk)
        new_kv = (k, v)
    elif cfg.kv_quant_bits:
        from repro.models.cache import quantize_kv
        kq, ks1 = quantize_kv(k, cfg.kv_quant_bits)
        vq, vs1 = quantize_kv(v, cfg.kv_quant_bits)
        ck = jax.lax.dynamic_update_slice(kv_cache["k"], kq, (0, cache_slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(kv_cache["v"], vq, (0, cache_slot, 0, 0))
        ksc = jax.lax.dynamic_update_slice(kv_cache["k_scale"], ks1,
                                           (0, cache_slot, 0))
        vsc = jax.lax.dynamic_update_slice(kv_cache["v_scale"], vs1,
                                           (0, cache_slot, 0))
        out = flash_attention(q, ck, cv, q_positions=positions,
                              k_positions=cache_positions, causal=True,
                              window=window, chunk=cfg.attn_chunk,
                              k_scale=ksc, v_scale=vsc)
        new_kv = {"k": ck, "v": cv, "k_scale": ksc, "v_scale": vsc}
    else:
        ck = jax.lax.dynamic_update_slice(kv_cache["k"], k, (0, cache_slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(kv_cache["v"], v, (0, cache_slot, 0, 0))
        out = flash_attention(q, ck, cv, q_positions=positions,
                              k_positions=cache_positions, causal=True,
                              window=window, chunk=cfg.attn_chunk)
        new_kv = {"k": ck, "v": cv}
    b, s = out.shape[0], out.shape[1]
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    return out @ p["wo"], new_kv


def cross_attention(p, x, cfg, *, kv=None, context=None):
    """Cross-attention (VLM image layers / enc-dec decoder).
    Either ``context`` (B, Sc, d) to project, or precomputed ``kv``=(k, v).
    No RoPE; bidirectional over context. Gated if p has 'gate'."""
    if kv is None:
        _, k, v = _qkv(p, context, context, cfg)
    else:
        k, v = kv
    b, s, _ = x.shape
    hq, dh = cfg.n_heads, cfg.head_dim
    q = (x @ p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = q.reshape(b, s, hq, dh)
    if cfg.qk_norm:
        q = rms_head_norm(p["q_scale"], q)
    qpos = jnp.zeros((b, s), jnp.int32)
    kpos = jnp.zeros((b, k.shape[1]), jnp.int32)
    out = flash_attention(q, k, v, q_positions=qpos, k_positions=kpos,
                          causal=False, chunk=cfg.attn_chunk)
    out = out.reshape(b, s, hq * dh) @ p["wo"]
    if "gate" in p:
        out = jnp.tanh(p["gate"].astype(out.dtype)) * out
    return out, (k, v)


def project_cross_kv(p, context, cfg):
    _, k, v = _qkv(p, context, context, cfg)
    return k, v


# ------------------------------------------------ multi-head latent attention
def init_mla(key, cfg):
    """DeepSeek-V2/V3 attention weights: the query (directly, or through
    its own low-rank path with ``q_lora_rank``), the joint down-projection
    to the latent ``c_kv`` and the shared RoPE key, the latent's RMSNorm,
    its up-projection to per-head keys and values, and the output."""
    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim, cfg.kv_lora_rank)
    dt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    p = {"wkv_a": dense_init(ks[1], (d, r + dr), dt),
         "kv_norm": jnp.ones((r,), dt),
         "wkv_b": dense_init(ks[2], (r, h * (dn + dv)), dt),
         "wo": dense_init(ks[3], (h * dv, d), dt)}
    if cfg.q_lora_rank:
        p["wq_a"] = dense_init(ks[0], (d, cfg.q_lora_rank), dt)
        p["q_norm"] = jnp.ones((cfg.q_lora_rank,), dt)
        p["wq_b"] = dense_init(ks[4], (cfg.q_lora_rank, h * (dn + dr)), dt)
    else:
        p["wq"] = dense_init(ks[0], (d, h * (dn + dr)), dt)
    return p


def mla_project(p, x, cfg, positions):
    """(q_nope (B,S,H,dn), q_rope (B,S,H,dr), c_kv (B,S,r), k_rope
    (B,S,dr)) of ``x``, float32, RoPE applied. ``c_kv`` is RMS-normed
    (the ``kv_a_layernorm``); it and ``k_rope`` are what the cache
    holds. RoPE rotates adjacent pairs of dimensions (``apply_rope``);
    the published code de-interleaves them first and rotates halves, a
    permutation of q and k alike that leaves the scores unchanged."""
    b, s, _ = x.shape
    h, dn, r = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    eps = cfg.norm_eps
    if cfg.q_lora_rank:
        q = mm(rms_head_norm(p["q_norm"], mm(x, p["wq_a"]), eps),
               p["wq_b"])
    else:
        q = mm(x, p["wq"])
    q = q.reshape(b, s, h, -1)
    kva = mm(x, p["wkv_a"])
    c_kv = rms_head_norm(p["kv_norm"], kva[..., :r], eps)
    q_rope = apply_rope(q[..., dn:], positions, cfg.rope_theta)
    k_rope = apply_rope(kva[:, :, None, r:], positions, cfg.rope_theta)
    return q[..., :dn], q_rope, c_kv, k_rope[:, :, 0]


def mla_attention(p, x, cfg, positions):
    """Causal MLA over the whole sequence (train / prefill). Keys and
    values are expanded from the latent per head; the one RoPE key is
    shared by every head. Products take the weights' dtype and accumulate
    in float32; scores are scaled by 1/sqrt(dn + dr). Returns (out
    (B,S,d) in x.dtype, (c_kv, k_rope)) for the cache. Runs under the
    named scope ``mla``."""
    b, s, _ = x.shape
    h, dn, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    wdt = p["wkv_b"].dtype
    with jax.named_scope("mla"):
        q_nope, q_rope, c_kv, k_rope = mla_project(p, x, cfg, positions)
        kv = mm(c_kv, p["wkv_b"]).reshape(b, s, h, dn + dv)
        q = jnp.concatenate([q_nope, q_rope], axis=-1).astype(wdt)
        k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :],
                                    (b, s, h, k_rope.shape[-1]))
        k = jnp.concatenate([kv[..., :dn], k_rope_h], axis=-1).astype(wdt)
        out = flash_attention(q, k, kv[..., dn:].astype(wdt),
                              q_positions=positions, k_positions=positions,
                              causal=True, chunk=cfg.attn_chunk)
        out = mm(out.reshape(b, s, h * dv), p["wo"]).astype(x.dtype)
    return out, (c_kv, k_rope)


def mla_decode(p, x, cfg, positions, cache, slot):
    """One new token against the latent cache {"ckv", "krope", "pos"}
    (ring buffers of length L; ``slot`` already modulo L): the token's
    latent is written at ``slot``, then attention runs in the latent
    space, the key up-projection absorbed into the query and the value
    up-projection applied after. Returns (out, new cache entry)."""
    b, s, _ = x.shape
    h, dn, dv, r = (cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim,
                    cfg.kv_lora_rank)
    q_nope, q_rope, c_kv, k_rope = mla_project(p, x, cfg, positions)
    pos = jax.lax.dynamic_update_slice(
        cache["pos"], positions.astype(jnp.int32), (0, slot))
    ckv = jax.lax.dynamic_update_slice(
        cache["ckv"], c_kv.astype(cache["ckv"].dtype), (0, slot, 0))
    krope = jax.lax.dynamic_update_slice(
        cache["krope"], k_rope.astype(cache["krope"].dtype), (0, slot, 0))
    w = p["wkv_b"].astype(jnp.float32).reshape(r, h, dn + dv)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w[..., :dn])
    scores = (jnp.einsum("bshr,blr->bhsl", q_lat, ckv.astype(jnp.float32))
              + jnp.einsum("bshd,bld->bhsl", q_rope,
                           krope.astype(jnp.float32)))
    scores = scores * (dn + cfg.qk_rope_head_dim) ** -0.5
    valid = (pos[:, None, None, :] >= 0) & (
        pos[:, None, None, :] <= positions[:, None, :, None])
    probs = jax.nn.softmax(jnp.where(valid, scores, NEG_INF), axis=-1)
    o_lat = jnp.einsum("bhsl,blr->bshr", probs, ckv.astype(jnp.float32))
    out = jnp.einsum("bshr,rhv->bshv", o_lat, w[..., dn:])
    out = mm(out.reshape(b, s, h * dv), p["wo"]).astype(x.dtype)
    return out, {"ckv": ckv, "krope": krope, "pos": pos}
