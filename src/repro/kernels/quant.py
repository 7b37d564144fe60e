"""Pallas TPU kernels: fused linear min-max quantize / dequantize (Eq. 1-2).

Memory-bound ops: fusing sub/scale/round/cast into one VMEM pass avoids three
HBM round-trips of the f32 intermediate. Tiles are (block_m, block_n) with
block_n a multiple of 128 (lane width); scales live in SMEM-like (1,1) blocks.

The TPU compiler has no direct cast between f32 and unsigned integers, so
codes pass through int32 inside the kernel (``to_codes``/``from_codes``).
The clipped codes lie in [0, 2^bits - 1], so both casts are exact and the
codes stay bitwise-equal to the XLA twins.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def to_codes(y, dtype):
    """Integral f32 values in [0, 2^bits - 1] -> unsigned codes, via int32."""
    return y.astype(jnp.int32).astype(dtype)


def from_codes(codes):
    """Unsigned codes -> f32, via int32."""
    return codes.astype(jnp.int32).astype(jnp.float32)


def code_scale(mn, mx, bits):
    """Codes per unit of x (Eq. 1). Computed outside the kernels, so the
    Pallas and XLA impls multiply by the same f32 scalar."""
    levels = float((1 << bits) - 1)
    return levels / jnp.maximum(jnp.asarray(mx, jnp.float32)
                                - jnp.asarray(mn, jnp.float32), 1e-12)


def code_step(mn, mx, bits):
    """Units of x per code (Eq. 2), shared by both impls like ``code_scale``."""
    levels = float((1 << bits) - 1)
    return (jnp.asarray(mx, jnp.float32) - jnp.asarray(mn, jnp.float32)) \
        / levels


def _scalar(v):
    return jnp.asarray(v, jnp.float32).reshape(1, 1)


def _quant_kernel(x_ref, mn_ref, scale_ref, o_ref, *, bits):
    x = x_ref[...].astype(jnp.float32)
    levels = float((1 << bits) - 1)
    y = jnp.clip(jnp.round((x - mn_ref[0, 0]) * scale_ref[0, 0]),
                 0.0, levels)
    o_ref[...] = to_codes(y, o_ref.dtype)


def _dequant_kernel(y_ref, mn_ref, step_ref, o_ref):
    y = from_codes(y_ref[...])
    o_ref[...] = (y * step_ref[0, 0] + mn_ref[0, 0]).astype(o_ref.dtype)


def _tiles(shape, bm, bn):
    m, n = shape
    return (pl.cdiv(m, bm), pl.cdiv(n, bn))


def quantize_xla(x, mn, mx, *, bits=8):
    """Decomposed-XLA quantize — the kernel's elementwise math in plain
    jnp, the fast path on CPU/GPU hosts (interpret-mode Pallas is for
    parity testing, not speed). Op-for-op identical to ``_quant_kernel``
    so the produced codes are bitwise-equal across impls."""
    levels = float((1 << bits) - 1)
    y = jnp.clip(jnp.round((x.astype(jnp.float32)
                            - jnp.asarray(mn, jnp.float32))
                           * code_scale(mn, mx, bits)), 0.0, levels)
    return y.astype(jnp.uint8 if bits <= 8 else jnp.uint16)


def dequantize_xla(y, mn, mx, *, bits=8, out_dtype=jnp.float32):
    """Decomposed-XLA dequantize, bitwise-equal to ``_dequant_kernel``."""
    out = y.astype(jnp.float32) * code_step(mn, mx, bits) \
        + jnp.asarray(mn, jnp.float32)
    return out.astype(out_dtype)


def quantize_2d(x, mn, mx, *, bits=8, block=(256, 512), interpret):
    """x: (M, N) float; mn/mx: () scalars. Returns uint8/16 codes (M, N)."""
    m, n = x.shape
    bm, bn = min(block[0], m), min(block[1], n)
    grid = _tiles((m, n), bm, bn)
    out_dtype = jnp.uint8 if bits <= 8 else jnp.uint16
    return pl.pallas_call(
        functools.partial(_quant_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(x, _scalar(mn), _scalar(code_scale(mn, mx, bits)))


def dequantize_2d(y, mn, mx, *, bits=8, out_dtype=jnp.float32,
                  block=(256, 512), interpret):
    m, n = y.shape
    bm, bn = min(block[0], m), min(block[1], n)
    grid = _tiles((m, n), bm, bn)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(y, _scalar(mn), _scalar(code_step(mn, mx, bits)))
