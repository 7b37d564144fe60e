"""Plain layers for the CNN references: NCHW activations, OIHW weights,
batch-statistic BatchNorm, all in ``jax.numpy`` and ``jax.lax`` with no
kernel, cache or batching of the program's.

An :class:`Arith` says how a reference computes: ``dtype`` holds every
parameter and activation, and convolutions and matrix products run at
``precision``. The configurations state float32 at the TPU's default
precision, which multiplies in one bfloat16 pass and accumulates in
float32; ``Arith(bfloat16)`` is their control, everything in bfloat16.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = {"default": jax.lax.Precision.DEFAULT}


@dataclasses.dataclass(frozen=True)
class Arith:
    dtype: object = jnp.float32
    precision: object = jax.lax.Precision.DEFAULT

    def cast(self, x):
        return jnp.asarray(x).astype(self.dtype)

    def conv(self, x, w, stride, pad, groups=1):
        """2-D convolution, ``x`` (B, C, H, W), ``w`` (O, C/groups, k, k)."""
        return jax.lax.conv_general_dilated(
            self.cast(x), self.cast(w), (stride, stride),
            [(pad, pad), (pad, pad)], feature_group_count=groups,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=self.precision)

    def matmul(self, subscripts, a, b):
        return jnp.einsum(subscripts, self.cast(a), self.cast(b),
                          precision=self.precision)

    def dense(self, x, w, b):
        return self.matmul("bi,io->bo", x, w) + self.cast(b)

    def batch_norm(self, x, scale, bias, eps=1e-5):
        """BatchNorm over (B, H, W) with the batch's own mean and biased
        variance, then the per-channel affine."""
        mu = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=(0, 2, 3), keepdims=True)
        y = (x - mu) / jnp.sqrt(var + jnp.asarray(eps, x.dtype))
        return y * self.cast(scale)[None, :, None, None] \
            + self.cast(bias)[None, :, None, None]


def relu(x):
    return jnp.maximum(x, 0)


def relu6(x):
    return jnp.clip(x, 0, 6)


def max_pool_3x3_s2(x):
    """3x3 max pool, stride 2, padding 1 (ResNet's stem)."""
    return jax.lax.reduce_window(
        x, jnp.asarray(-jnp.inf, x.dtype), jax.lax.max, (1, 1, 3, 3),
        (1, 1, 2, 2), [(0, 0), (0, 0), (1, 1), (1, 1)])


def global_avg_pool(x):
    return jnp.mean(x, axis=(2, 3))


def conv_weight(key, cout, cin_per_group, k):
    """He-normal (fan-in) weights in OIHW."""
    fan_in = cin_per_group * k * k
    return jax.random.normal(key, (cout, cin_per_group, k, k),
                             jnp.float32) * np.sqrt(2.0 / fan_in)


def bn_params(key, ch):
    """A per-channel affine near identity, drawn so that a fault that
    drops it shows."""
    k1, k2 = jax.random.split(key)
    return {"scale": 1.0 + 0.1 * jax.random.normal(k1, (ch,), jnp.float32),
            "bias": 0.1 * jax.random.normal(k2, (ch,), jnp.float32)}


def fc_params(key, cin, cout):
    k1, k2 = jax.random.split(key)
    return {"w": jax.random.normal(k1, (cin, cout), jnp.float32)
            / np.sqrt(cin),
            "b": 0.01 * jax.random.normal(k2, (cout,), jnp.float32)}
