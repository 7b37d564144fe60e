"""Pallas TPU kernel: Mamba-2 SSD intra-chunk contribution.

The quadratic hot spot of the SSD algorithm (models/ssm.ssd_chunked):

    y[i] = sum_{j<=i} (C_i . B_j) * exp(la_i - la_j) * dt_j * x_j

Grid (batch, n_chunks, heads) with heads innermost; the (Q, Q) C.B^T Gram
matrix is head-independent, so it is computed once per (batch, chunk) into a
VMEM scratch tile on the first head step and reused across heads. Per-head
working set: (Q,Q) decay+weights and a (Q,P) x/out tile — VMEM-sized for
Q=256, P<=128 (Q multiple of 8/128 lanes for MXU alignment).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(c_ref, b_ref, la_ref, dt_ref, x_ref, o_ref, cb_ref):
    h = pl.program_id(2)

    @pl.when(h == 0)
    def _gram():
        c = c_ref[0, 0].astype(jnp.float32)          # (Q, N)
        b = b_ref[0, 0].astype(jnp.float32)          # (Q, N)
        cb_ref[...] = jnp.dot(c, b.T, preferred_element_type=jnp.float32)

    la = la_ref[0, 0, :, 0].astype(jnp.float32)      # (Q,)
    dt = dt_ref[0, 0, :, 0].astype(jnp.float32)      # (Q,)
    q = la.shape[0]
    seg = la[:, None] - la[None, :]                  # (Q, Q) la_i - la_j
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    seg = jnp.where(ii >= jj, seg, NEG_INF)
    w = cb_ref[...] * jnp.exp(seg) * dt[None, :]     # (Q, Q)
    x = x_ref[0, 0, :, 0].astype(jnp.float32)        # (Q, P)
    o_ref[0, 0, :, 0] = jnp.dot(w, x, preferred_element_type=jnp.float32
                                ).astype(o_ref.dtype)


def ssd_intra(xh, dt, la, Bm, Cm, *, interpret):
    """xh: (B, NC, Q, H, P); dt, la: (B, NC, Q, H) f32;
    Bm, Cm: (B, NC, Q, N). Returns y_intra (B, NC, Q, H, P) f32."""
    b, nc, q, h, p = xh.shape
    n = Bm.shape[-1]
    return pl.pallas_call(
        _kernel,
        grid=(b, nc, h),
        in_specs=[
            pl.BlockSpec((1, 1, q, n), lambda bi, ci, hi: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda bi, ci, hi: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda bi, ci, hi: (bi, ci, 0, hi)),
            pl.BlockSpec((1, 1, q, 1), lambda bi, ci, hi: (bi, ci, 0, hi)),
            pl.BlockSpec((1, 1, q, 1, p),
                         lambda bi, ci, hi: (bi, ci, 0, hi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q, 1, p),
                               lambda bi, ci, hi: (bi, ci, 0, hi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nc, q, h, p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((q, q), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(Cm, Bm, la, dt, xh)
