"""Pallas TPU kernel: fused compressor encode — z = quantize(x @ W_enc).

This is the entire UE-side cost of the paper's compressor for transformer
hidden states: a (T, d) x (d, d') bottleneck matmul (the 1x1 conv) fused
with Eq. 1 quantization so the f32 bottleneck activation never leaves VMEM.

Blocked matmul: grid (M/bm, N/bn, K/bk) with the K dimension innermost
("arbitrary" semantics), f32 accumulation in a VMEM scratch tile, quantize-
and-store on the last K step. Block sizes default to MXU-aligned multiples
of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant import code_scale, to_codes


def _kernel(x_ref, w_ref, mn_ref, scale_ref, o_ref, acc_ref, *, bits, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                            w_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _store():
        levels = float((1 << bits) - 1)
        y = jnp.clip(jnp.round((acc_ref[...] - mn_ref[0, 0])
                               * scale_ref[0, 0]), 0.0, levels)
        o_ref[...] = to_codes(y, o_ref.dtype)


def bottleneck_encode(x, w, mn, mx, *, bits=8, block=(256, 128, 512),
                      interpret):
    """x: (T, d); w: (d, d'); mn/mx: calibrated quantization range.
    Returns uint8 codes (T, d')."""
    t, d = x.shape
    dp = w.shape[1]
    bm = min(block[0], t)
    bn = min(block[1], dp)
    bk = min(block[2], d)
    grid = (pl.cdiv(t, bm), pl.cdiv(dp, bn), pl.cdiv(d, bk))
    scal = lambda v: jnp.asarray(v, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        functools.partial(_kernel, bits=bits, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, dp), jnp.uint8 if bits <= 8
                                       else jnp.uint16),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, scal(mn), scal(code_scale(mn, mx, bits)))
