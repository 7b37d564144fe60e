"""Fused int8 dequant-matmul kernel for the distilled dispatch trunk.

The train-big/serve-small deployment path (``rl/distill.py``) serves the
entity policy as a small flat MLP over ``observe_per_ue``-style rows,
with every weight matrix stored as linear min-max int8 codes (paper
Eq. 1-2, the same scheme ``quant.py`` applies to intermediate features).
The naive serving chain — dequantize each W to f32 in HBM, then run the
MLP (``ref.flat_trunk_ref``) — pays one full-precision weight
materialization per layer per forward. This kernel fuses the whole
student forward:

  * per-layer dequant ``w = codes * ((mx - mn) / levels) + mn`` in
    VMEM/registers — the f32 weights never exist in HBM,
  * the matmul chain with tanh between layers (linear last), emitting
    the full head-logit row block (every ``HybridActionSpace`` head in
    ONE pass — no per-pair scorer, no attention pooling),

gridded over row blocks of the batch, so batch-10k serving streams rows
through a resident quantized weight set.

``flat_trunk_xla`` is the same computation in plain jnp — the fast path
on CPU/GPU hosts. Both impls share the exact dequant association, so
pallas-vs-xla parity is bitwise on the weight dequant; both match
``ref.flat_trunk_ref`` to f32 tolerance. Layer count and widths are
static (baked into the grid), matching the fixed-E deployment contract
of the distilled trunk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant import code_step, from_codes


def _dot(a, b):
    """f32 matmul at full precision, so the compiled kernel meets the f32
    oracle as interpret mode does."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _trunk_kernel(*refs, n_layers):
    x_ref, o_ref = refs[0], refs[-1]
    h = x_ref[...].astype(jnp.float32)
    for i in range(n_layers):
        codes_ref, mn_ref, step_ref, b_ref = refs[1 + 4 * i:5 + 4 * i]
        w = from_codes(codes_ref[...]) * step_ref[0, 0] + mn_ref[0, 0]
        h = _dot(h, w) + b_ref[...]
        if i < n_layers - 1:
            h = jnp.tanh(h)
    o_ref[...] = h


def flat_trunk_pallas(x, codes, mns, mxs, bs, *, bits=8, block_n=512,
                      interpret):
    """Fused quantized trunk forward -> (M, W) f32 head columns.

    x: (M, F) feature rows (any float dtype); codes: per-layer integer
    weight codes ((nin_i, nout_i), uint8/16); mns/mxs: per-layer ()
    calibration scalars; bs: per-layer (nout_i,) f32 biases (biases stay
    full precision — they are O(width), the weights are O(width^2))."""
    f32 = jnp.float32
    m, feat = x.shape
    n_layers = len(codes)
    width = codes[-1].shape[1]
    bm = max(1, min(block_n, m))
    grid = (pl.cdiv(m, bm),)
    full = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    row = lambda w_: pl.BlockSpec((bm, w_), lambda i: (i, 0))
    in_specs = [row(feat)]
    args = [x.astype(f32)]
    for i in range(n_layers):
        nin, nout = codes[i].shape
        in_specs += [full((nin, nout)), full((1, 1)), full((1, 1)),
                     full((1, nout))]
        args += [codes[i], jnp.asarray(mns[i], f32).reshape(1, 1),
                 code_step(mns[i], mxs[i], bits).reshape(1, 1),
                 jnp.asarray(bs[i], f32).reshape(1, nout)]
    return pl.pallas_call(
        functools.partial(_trunk_kernel, n_layers=n_layers),
        grid=grid,
        in_specs=in_specs,
        out_specs=row(width),
        out_shape=jax.ShapeDtypeStruct((m, width), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*args)


def flat_trunk_xla(x, codes, mns, mxs, bs, *, bits=8):
    """The decomposed trunk forward in plain jnp — same per-layer dequant
    association as the kernel (``codes * ((mx - mn) / levels) + mn``), so
    the two impls agree bitwise on the dequantized weights."""
    f32 = jnp.float32
    h = x.astype(f32)
    n_layers = len(codes)
    for i in range(n_layers):
        w = codes[i].astype(f32) * code_step(mns[i], mxs[i], bits) \
            + jnp.asarray(mns[i], f32)
        h = h @ w + jnp.asarray(bs[i], f32)
        if i < n_layers - 1:
            h = jnp.tanh(h)
    return h
