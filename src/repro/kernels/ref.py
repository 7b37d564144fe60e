"""Pure-jnp oracles for every Pallas kernel (allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize_ref(x, mn, mx, bits=8):
    """Linear min-max quantization (paper Eq. 1) with static calibration."""
    levels = (1 << bits) - 1
    scale = levels / jnp.maximum(mx - mn, 1e-12)
    y = jnp.clip(jnp.round((x.astype(jnp.float32) - mn) * scale), 0, levels)
    return y.astype(jnp.uint8 if bits <= 8 else jnp.uint16)


def dequantize_ref(y, mn, mx, bits=8):
    """Paper Eq. 2."""
    levels = (1 << bits) - 1
    return y.astype(jnp.float32) * (mx - mn) / levels + mn


def flat_trunk_ref(x, codes, mns, mxs, bs, bits=8):
    """Naive oracle for the fused int8 dequant-matmul dispatch trunk
    (``kernels/flat_trunk.py``): dequantize every weight matrix to f32
    via ``dequantize_ref`` (paper Eq. 2), then run the plain tanh MLP
    (linear last layer) — each full-precision W materializes in HBM."""
    h = x.astype(jnp.float32)
    for i in range(len(codes)):
        w = dequantize_ref(codes[i], jnp.float32(mns[i]),
                           jnp.float32(mxs[i]), bits)
        h = h @ w + jnp.asarray(bs[i], jnp.float32)
        if i < len(codes) - 1:
            h = jnp.tanh(h)
    return h


def grouped_matmul_ref(lhs, rhs, group_sizes):
    """Each row of ``lhs`` times the ``rhs`` of the group it falls in
    (groups are consecutive runs of ``group_sizes`` rows), float32."""
    ends = jnp.cumsum(group_sizes)
    group = jnp.searchsorted(ends, jnp.arange(lhs.shape[0]), side="right")
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for gi in range(rhs.shape[0]):
        y = jnp.dot(lhs.astype(jnp.float32), rhs[gi].astype(jnp.float32))
        out = jnp.where((group == gi)[:, None], y, out)
    return out


def bottleneck_encode_ref(x, w, mn, mx, bits=8):
    """Fused compressor encode: (T, d) @ (d, d') then quantize."""
    z = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))
    return quantize_ref(z, mn, mx, bits)


def ssd_intra_ref(xh, dt, la, Bm, Cm):
    """SSD intra-chunk oracle (mirrors models/ssm.ssd_chunked's intra part).
    xh: (B, NC, Q, H, P); dt, la: (B, NC, Q, H); Bm, Cm: (B, NC, Q, N)."""
    q = xh.shape[2]
    seg = la[:, :, :, None, :] - la[:, :, None, :, :]   # (B,NC,i,j,H)
    mask = jnp.tril(jnp.ones((q, q), bool))
    seg = jnp.where(mask[None, None, :, :, None], seg, -1e30)
    cb = jnp.einsum("bcin,bcjn->bcij", Cm, Bm)
    w = cb[..., None] * jnp.exp(seg) * dt[:, :, None, :, :]
    return jnp.einsum("bcijh,bcjhp->bcihp", w, xh)


def decode_attention_ref(q, k, v, pos, idx):
    """GQA decode attention over a (ring) KV cache.

    q: (B, Hq, D) single query token; k, v: (B, S, Hkv, D);
    pos: (B, S) absolute positions (-1 = empty slot); idx: scalar int32.
    Returns (B, Hq, D) f32."""
    b, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qf = q.reshape(b, hkv, g, d).astype(jnp.float32) * (d ** -0.5)
    s = jnp.einsum("bhgd,bshd->bhgs", qf, k.astype(jnp.float32))
    valid = (pos >= 0) & (pos <= idx)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, d)


def pair_scorer_ref(ue_emb, d, work, active, geom, consts,
                    w_srv, b_srv, w1, b1, w2, b2):
    """Naive (UE, server) pair scorer: the oracle for
    ``pair_scorer.pair_scorer_pallas`` / ``pair_scorer_xla``.

    Deliberately mirrors the DEFAULT entity path op-for-op — the edge
    tensor build of ``MECEnv.observe_entities`` followed by
    ``nets.entity_trunk``'s materialized (N, E, d_ue+S+3) pair concat and
    scorer MLP — so fused-vs-ref parity is also fused-vs-default parity.
    ``consts`` is the env-built 8-vector (see kernels/pair_scorer.py);
    ``active`` enters only through the per-(server, channel) occupancy
    scalar. Returns (route_logits (N, E), srv_emb (E, S))."""
    f32 = jnp.float32
    ue_emb = ue_emb.astype(f32)
    d = d.astype(f32)
    work = work.astype(f32)
    active = active.astype(f32)
    geom = geom.astype(f32)
    consts = consts.astype(f32)
    n, d_ue = ue_emb.shape
    e = geom.shape[0]
    per_slot = active.sum() / consts[5]
    srv_rows = jnp.concatenate([
        geom * jnp.stack([jnp.float32(1.0), jnp.float32(1.0), consts[7]]),
        jnp.broadcast_to(per_slot, (e,))[:, None],
    ], axis=1)
    srv = jnp.tanh(srv_rows @ w_srv + b_srv)                   # (E, S)
    dist_ne = d[:, None] * geom[None, :, 0]                    # (N, E)
    g_ne = jnp.power(jnp.maximum(dist_ne, 1.0), -consts[0])
    rate = (geom[:, 1] * consts[3])[None, :] \
        * jnp.log2(1.0 + consts[1] * g_ne / consts[2])
    te = work[:, None] * geom[None, :, 2] / consts[4]
    edge = jnp.stack([dist_ne / consts[6], rate, te], axis=-1)
    pair = jnp.concatenate([
        jnp.broadcast_to(ue_emb[:, None, :], (n, e, d_ue)),
        jnp.broadcast_to(srv[None, :, :], (n, e, srv.shape[-1])),
        edge,
    ], axis=-1)
    h = jnp.tanh(pair @ w1 + b1)
    logits = (h @ w2 + b2)[..., 0]                             # (N, E)
    return logits, srv
