"""Basic layers: norms, MLPs, RoPE, initializers. Pure-function + pytree style."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------- init utils
def dense_init(key, shape, dtype, scale: float = 1.0):
    fan_in = shape[0] if len(shape) >= 2 else 1
    std = scale / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------- norms
def init_norm(cfg, d=None):
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), dtype_of(cfg.param_dtype))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype_of(cfg.param_dtype))
    return p


def apply_norm(p, x, cfg, eps=None):
    eps = cfg.norm_eps if eps is None else eps
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def mm(x, w):
    """x @ w with x cast to the weight's dtype and float32 accumulation,
    returned in float32: bfloat16 weights multiply bfloat16 inputs on
    every backend, as the MXU does."""
    return jnp.matmul(x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def rms_head_norm(scale, x, eps: float = 1e-6):
    """RMSNorm over the last axis in float32, returned in x's dtype: the
    per-head qk_norm (x: (..., d_head)) and MLA's latent norms."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------- MLP
def init_mlp(key, cfg, d_ff=None):
    d, f = cfg.d_model, (d_ff or cfg.d_ff)
    dt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    p = {"wo": dense_init(ks[2], (f, d), dt)}
    if cfg.act == "swiglu":
        p["wi"] = dense_init(ks[0], (d, f), dt)
        p["wg"] = dense_init(ks[1], (d, f), dt)
    else:
        p["wi"] = dense_init(ks[0], (d, f), dt)
    return p


def apply_mlp(p, x, cfg):
    if x.dtype != p["wo"].dtype and cfg.act == "swiglu":
        # a float32 residual stream over bfloat16 weights: products in
        # bfloat16, accumulated and activated in float32
        h = jax.nn.silu(mm(x, p["wg"])) * mm(x, p["wi"])
        return mm(h, p["wo"]).astype(x.dtype)
    if cfg.act == "swiglu":
        h = jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])
    else:
        h = jax.nn.gelu(x @ p["wi"])
    return h @ p["wo"]


# ---------------------------------------------------------------- RoPE
def rope_freqs(positions, d_head, theta, fraction=1.0):
    """positions: (..., S) int32 -> cos/sin (..., S, d_rot//2)."""
    d_rot = int(d_head * fraction)
    d_rot -= d_rot % 2
    inv = 1.0 / (theta ** (np.arange(0, d_rot, 2, dtype=np.float32) / d_rot))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang), d_rot


def apply_rope(x, positions, theta, fraction=1.0):
    """x: (B, S, H, D); positions: (B, S)."""
    cos, sin, d_rot = rope_freqs(positions, x.shape[-1], theta, fraction)
    if d_rot == 0:
        return x
    xr, xp = x[..., :d_rot], x[..., d_rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    o1 = x1 * cos - x2 * sin
    o2 = x1 * sin + x2 * cos
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)
