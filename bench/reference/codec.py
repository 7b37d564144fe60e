"""The split boundary's codec, written from Hao et al. (arXiv:2205.11854)
§II as the plain reference: a linear autoencoder over channels (a 1x1
conv), fitted in closed form by PCA, and linear min-max quantization
(Eq. 1-2) with one (min, max) pair per batch.
"""
from __future__ import annotations

import jax.numpy as jnp


def pca_autoencoder(feats, ch_prime):
    """Encoder (C, C') and decoder (C', C) from the top ``ch_prime``
    principal directions of ``feats`` (B, C, H, W), samples over B*H*W,
    by an eigendecomposition of the channel covariance."""
    c = feats.shape[1]
    f = jnp.moveaxis(feats, 1, -1).reshape(-1, c)
    f = f - f.mean(axis=0)
    cov = f.T @ f / f.shape[0]
    _, vecs = jnp.linalg.eigh(cov)             # ascending eigenvalues
    pcs = vecs[:, ::-1][:, :ch_prime]
    return {"enc": pcs, "dec": pcs.T}


def encode(enc, feat):
    return jnp.einsum("bchw,cd->bdhw", feat, enc.astype(feat.dtype))


def decode(dec, z, ar):
    """The decoder's 1x1 conv, as ``ar`` computes products."""
    return ar.matmul("bdhw,dc->bchw", z, dec)


def quantize(z, bits):
    """Eq. 1 over the whole batch: (codes as uint8, min, max)."""
    mn, mx = jnp.min(z), jnp.max(z)
    levels = (1 << bits) - 1
    y = jnp.clip(jnp.round((z - mn) * (levels / (mx - mn))), 0, levels)
    return y.astype(jnp.uint8), mn, mx


def dequantize(codes, mn, mx, bits, dtype=jnp.float32):
    """Eq. 2: codes back to values, in ``dtype``."""
    step = (jnp.asarray(mx, dtype) - jnp.asarray(mn, dtype)) \
        / jnp.asarray((1 << bits) - 1, dtype)
    return codes.astype(dtype) * step + jnp.asarray(mn, dtype)
