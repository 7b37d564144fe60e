"""The routed experts' grouped matmul on the TPU: the megablox Pallas
``gmm`` that JAX ships (``jax.experimental.pallas.ops.tpu.megablox``), at
tiles chosen from the problem's shape, with ``ragged_dot``'s gradient.

The kernel's grid is (n-tiles, m-tiles, k-tiles). It visits an m-tile
once for every group with rows in it, and each visit stores only its own
group's rows. With ``tk`` the whole of k, an expert's (k, tn) weight block
keeps its block index over all of that expert's m-tiles, and Pallas
fetches it once.

The shipped kernel's own VJP (``megablox.ops.gmm``) runs the float32
cotangent through the forward's tiles, which were sized for bfloat16
blocks, and rounds it to bfloat16 for the weights' gradient. The backward
here is ``jax.lax.ragged_dot``'s VJP on the same groups instead: the
exact gradient of the same function, tiled by XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _megablox_gmm


TM = 256                # rows of an m-tile
TN = 512                # columns of an n-tile, where n is wider
VMEM_BUDGET = 12 << 20  # bytes, of the kernel's 16 MiB default scoped VMEM


def _cdiv(a, b):
    return -(-a // b)


def vmem_bytes(tm, tk, tn, itemsize=2):
    """VMEM the kernel takes, as fitted to which bfloat16 tiles the v5e's
    compiler placed within the 16 MiB default: three (tm, tk) row blocks,
    two (tk, tn) weight blocks and three float32 (tm, tn) tiles (the
    output block, double-buffered, and the accumulator)."""
    return (3 * tm * tk * itemsize + 2 * tk * tn * itemsize
            + 3 * tm * tn * 4)


def tiling(m, k, n, itemsize=2):
    """(tm, tk, tn) for ``lhs`` (m, k) times groups of (k, n), of
    ``itemsize`` bytes an element.

    ``tm`` is ``TM``, or m rounded up to 16 rows where that is less
    (``gmm`` pads m to a multiple of it). ``tn`` is n up to ``TN``, else
    ``TN``. ``tk`` is the whole of k where the blocks fit
    ``VMEM_BUDGET``, so that each expert's weights are fetched once for
    all its m-tiles; else it halves, in multiples of 128 lanes, until
    they fit. The rule won a sweep on a v5e at the Moonlight cell's shapes
    (the times are in ``ops.grouped_matmul``'s docstring)."""
    tm = min(TM, _cdiv(m, 16) * 16)
    tn = n if n <= TN else TN
    tk = k
    while vmem_bytes(tm, tk, tn, itemsize) > VMEM_BUDGET and tk > 128:
        tk = max(128, _cdiv(tk // 2, 128) * 128)
    return tm, tk, tn


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gmm(lhs, rhs, group_sizes, tiles=None, interpret=False):
    """(m, n) float32: ``lhs`` (m, k) times ``rhs`` (g, k, n) by groups.

    ``group_sizes`` (g,) int32 gives the rows of each group in order, and
    must then cover every row. A ``group_sizes`` of length g + 1 counts,
    last, rows that belong to no group: those come out zero. ``tiles``
    (tm, tk, tn) defaults to ``tiling(m, k, n)``."""
    m, k = lhs.shape
    tm, tk, tn = tiles or tiling(m, k, rhs.shape[2], lhs.dtype.itemsize)
    pad = -m % tm
    out = _megablox_gmm(jnp.pad(lhs, ((0, pad), (0, 0))) if pad else lhs,
                        rhs, group_sizes, jnp.float32, (tm, tk, tn),
                        interpret=interpret)
    return out[:m] if pad else out


def _gmm_fwd(lhs, rhs, group_sizes, tiles, interpret):
    return gmm(lhs, rhs, group_sizes, tiles, interpret), (lhs, rhs,
                                                          group_sizes)


def _gmm_bwd(tiles, interpret, res, grad):
    lhs, rhs, group_sizes = res
    _, vjp = jax.vjp(lambda x, w: jax.lax.ragged_dot(
        x, w, group_sizes[:rhs.shape[0]],
        preferred_element_type=jnp.float32), lhs, rhs)
    return (*vjp(grad), None)


gmm.defvjp(_gmm_fwd, _gmm_bwd)
