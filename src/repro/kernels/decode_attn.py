"""Pallas TPU kernel: GQA flash-decode attention over a (ring) KV cache.

The edge server's serving hot spot: one query token against a long cache.
Grid (B, Hkv, S/bs) with the cache-length dimension innermost; online
softmax with running (m, l, acc) in VMEM scratch; the ring-buffer position
map (pos, -1 = empty) provides masking, so full and sliding-window caches
use the same kernel. Head-dim tiles are MXU/lane aligned (D multiple of 128
for full utilization; smaller D still works via padding by pallas).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(idx_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, ns):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
    k = k_ref[0, :, 0].astype(jnp.float32)       # (bs, D)
    v = v_ref[0, :, 0].astype(jnp.float32)       # (bs, D)
    pos = pos_ref[0]                             # (bs,)
    d = q.shape[-1]
    scores = jnp.dot(q * (d ** -0.5), k.T,
                     preferred_element_type=jnp.float32)       # (G, bs)
    valid = (pos >= 0) & (pos <= idx_ref[0, 0])
    scores = jnp.where(valid[None, :], scores, NEG_INF)

    m_prev = m_ref[...]                          # (G, 1)
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    p = jnp.exp(scores - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(s == ns - 1)
    def _store():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


def decode_attention(q, k, v, pos, idx, *, block_s=512, interpret):
    """q: (B, Hq, D); k, v: (B, S, Hkv, D); pos: (B, S) int32; idx: scalar.
    Returns (B, Hq, D) f32."""
    b, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    bs = min(block_s, s)
    ns = pl.cdiv(s, bs)
    qr = q.reshape(b, hkv, g, d)
    idx2 = jnp.asarray(idx, jnp.int32).reshape(1, 1)
    out = pl.pallas_call(
        functools.partial(_kernel, ns=ns),
        grid=(b, hkv, ns),
        in_specs=[
            pl.BlockSpec((1, 1), lambda bi, h, si: (0, 0)),
            pl.BlockSpec((1, 1, g, d), lambda bi, h, si: (bi, h, 0, 0)),
            pl.BlockSpec((1, bs, 1, d), lambda bi, h, si: (bi, si, h, 0)),
            pl.BlockSpec((1, bs, 1, d), lambda bi, h, si: (bi, si, h, 0)),
            pl.BlockSpec((1, bs), lambda bi, h, si: (bi, si)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda bi, h, si: (bi, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(idx2, qr, k, v, pos)
    return out.reshape(b, hq, d)
