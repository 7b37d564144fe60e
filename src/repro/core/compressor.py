"""Lightweight autoencoder-based intermediate feature compression (paper §2).

Encoder/decoder are single 1x1 convolutions over the channel dim — for CNN
features (B, C, H, W) that is an einsum over C; for transformer hidden states
(B, S, d) it is a d -> d' matmul (a 1x1 conv over channels IS a matmul, which
on TPU maps straight onto the MXU — see kernels/bottleneck.py for the fused
Pallas version).

Quantization: linear min-max to c_q bits (Eq. 1-2). Overall rate
R = (ch * 32) / (ch' * c_q) (Eq. 3).

Training (paper §2.4): stage 1 optimizes the AE with the backbone frozen on
L2(feature, reconstruction) + xi * CE(prediction); stage 2 fine-tunes
everything with a small LR.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import cnn as cnn_lib
from repro.optim import adamw_init, adamw_update


# ------------------------------------------------------------ quantization
def quantize(x, bits, minv=None, maxv=None):
    """Eq. 1. Returns (codes, minv, maxv); codes are integers in [0, 2^b-1],
    stored in the smallest sufficient int dtype."""
    minv = jnp.min(x) if minv is None else minv
    maxv = jnp.max(x) if maxv is None else maxv
    levels = (1 << bits) - 1
    scale = levels / jnp.maximum(maxv - minv, 1e-12)
    y = jnp.round((x - minv) * scale)
    y = jnp.clip(y, 0, levels)
    dt = jnp.uint8 if bits <= 8 else jnp.uint16
    return y.astype(dt), minv, maxv


def dequantize(y, bits, minv, maxv):
    """Eq. 2."""
    levels = (1 << bits) - 1
    return y.astype(jnp.float32) * (maxv - minv) / levels + minv


def compression_rate(ch, ch_prime, bits):
    """Eq. 3: R = R_c * R_q."""
    return (ch * 32.0) / (ch_prime * bits)


# --------------------------------------------------------------- AE params
def init_autoencoder(key, ch, ch_prime):
    k1, k2 = jax.random.split(key)
    s = 1.0 / jnp.sqrt(ch)
    return {"enc": jax.random.normal(k1, (ch, ch_prime)) * s,
            "dec": jax.random.normal(k2, (ch_prime, ch)) * (1.0 / jnp.sqrt(ch_prime))}


def pca_init_autoencoder(feats, ch_prime):
    """Closed-form optimal LINEAR autoencoder: top principal components of
    the boundary features (beyond-paper: the paper random-inits and trains;
    PCA init converges in a fraction of the steps). feats: (B, C, H, W)
    CNN features (channels at axis 1, samples over B*H*W) or (..., C)
    channel-last (samples over all leading axes)."""
    if feats.ndim == 4:  # (B, C, H, W) -> samples over B*H*W
        f = jnp.moveaxis(feats, 1, -1).reshape(-1, feats.shape[1])
    else:                # (..., C) channel-last
        f = feats.reshape(-1, feats.shape[-1])
    mu = f.mean(0)
    _, _, vt = jnp.linalg.svd(f - mu, full_matrices=False)
    pcs = vt[:ch_prime].T
    return {"enc": pcs, "dec": pcs.T}


def encode(ae, feat):
    """feat: (B, C, H, W) or (B, S, C) -> bottleneck along channel dim."""
    if feat.ndim == 4:
        return jnp.einsum("bchw,cd->bdhw", feat, ae["enc"])
    return feat @ ae["enc"]


def decode(ae, z):
    """z: (B, D, H, W) or (B, S, D) -> features, under the named scope
    ``ae_decode``."""
    with jax.named_scope("ae_decode"):
        if z.ndim == 4:
            return jnp.einsum("bdhw,dc->bchw", z, ae["dec"])
        return z @ ae["dec"]


def roundtrip(ae, feat, bits=None):
    """encode -> (optional quantize/dequantize) -> decode."""
    z = encode(ae, feat)
    if bits is not None:
        q, mn, mx = quantize(z, bits)
        z = dequantize(q, bits, mn, mx).astype(feat.dtype)
    return decode(ae, z)


# ------------------------------------------------- two-stage training (CNN)
def ae_loss(ae, backbone_params, model, split_module, x, labels, xi=0.1,
            bits=None):
    """Paper Eq. 4 for a CNN backbone split after module `split_module`."""
    feat = cnn_lib.forward(model, backbone_params, x, upto=split_module + 1)
    feat_hat = roundtrip(ae, feat, bits)
    logits = cnn_lib.forward_from(model, backbone_params, feat_hat,
                                  split_module + 1)
    l2 = jnp.sqrt(jnp.sum(jnp.square(feat - feat_hat)) + 1e-12) / x.shape[0]
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    ce = jnp.mean(lse - tgt)
    return l2 + xi * ce, (l2, ce)


def train_autoencoder(key, model, backbone_params, split_module, data_iter,
                      *, ch, ch_prime, steps=100, lr=1e-3, xi=0.1,
                      finetune_steps=0, ft_lr=1e-4, pca_init=True):
    """Stage 1: AE only, frozen backbone. Stage 2 (finetune_steps>0): joint.
    data_iter yields (x, labels). Returns (ae, backbone_params, logs)."""
    if pca_init:
        x0, _ = next(data_iter)
        feats = cnn_lib.forward(model, backbone_params, x0,
                                upto=split_module + 1)
        ae = pca_init_autoencoder(feats, ch_prime)
    else:
        ae = init_autoencoder(key, ch, ch_prime)
    opt = adamw_init(ae)
    logs = []

    @jax.jit
    def step1(ae, opt, x, y):
        (loss, (l2, ce)), g = jax.value_and_grad(
            ae_loss, has_aux=True)(ae, backbone_params, model, split_module,
                                   x, y, xi)
        ae, opt = adamw_update(g, opt, ae, lr, weight_decay=0.0)
        return ae, opt, loss, l2, ce

    for _ in range(steps):
        x, y = next(data_iter)
        ae, opt, loss, l2, ce = step1(ae, opt, x, y)
        logs.append({"stage": 1, "loss": float(loss), "l2": float(l2),
                     "ce": float(ce)})

    if finetune_steps:
        joint = {"ae": ae, "bb": backbone_params}
        jopt = adamw_init(joint)

        def jloss(j, x, y):
            return ae_loss(j["ae"], j["bb"], model, split_module, x, y, xi)

        @jax.jit
        def step2(j, o, x, y):
            (loss, (l2, ce)), g = jax.value_and_grad(jloss, has_aux=True)(j, x, y)
            j, o = adamw_update(g, o, j, ft_lr, weight_decay=0.0)
            return j, o, loss

        for _ in range(finetune_steps):
            x, y = next(data_iter)
            joint, jopt, loss = step2(joint, jopt, x, y)
            logs.append({"stage": 2, "loss": float(loss)})
        ae, backbone_params = joint["ae"], joint["bb"]

    return ae, backbone_params, logs


def measure_rate_distortion(model, backbone_params, data_iter_fn,
                            eval_batch_fn, *, points=None, ratios=(4, 8, 16),
                            bits=8, steps=30, lr=3e-3, xi=0.1, acc_drop=0.02,
                            base_acc=None, seed=0):
    """Per-split-point compressor rate-distortion by the paper's Fig. 4
    selection rule: at each candidate point, train an AE per channel-
    reduction ratio and keep the HIGHEST rate whose accuracy stays within
    `acc_drop` of the no-AE baseline; quant-only R = 32/bits (ch' = ch)
    is the fallback when no ratio qualifies.

    data_iter_fn(pi) -> (x, labels) iterator, fresh stream per point;
    eval_batch_fn(pi) -> (x, labels) batch for the accuracy check.
    Returns one row per split point
      {point, module, channels, ch_prime, bits, rate, acc, base_acc}
    consumable directly as measured_cnn_split_table(..., rd=rows)."""
    points = list(model.split_after) if points is None else list(points)
    if base_acc is None:
        accs = []
        for pi in range(len(points)):
            x, y = eval_batch_fn(pi)
            logits = cnn_lib.forward(model, backbone_params, x)
            accs.append(float(jnp.mean((jnp.argmax(logits, -1) == y))))
        base_acc = float(sum(accs) / len(accs))
    rows = []
    for pi, k in enumerate(points):
        x_eval, y_eval = eval_batch_fn(pi)
        ch = int(cnn_lib.forward(model, backbone_params, x_eval[:1],
                                 upto=k + 1).shape[1])
        best = {"ch_prime": ch, "rate": compression_rate(ch, ch, bits),
                "acc": base_acc}
        for rc in ratios:
            chp = max(1, ch // rc)
            ae, _, _ = train_autoencoder(
                jax.random.PRNGKey(seed + pi * 10 + rc), model,
                backbone_params, k, data_iter_fn(pi), ch=ch, ch_prime=chp,
                steps=steps, lr=lr, xi=xi)
            acc = float(accuracy_with_ae(model, backbone_params, ae, k,
                                         x_eval, y_eval, bits=bits))
            rate = compression_rate(ch, chp, bits)
            if acc >= base_acc - acc_drop and rate > best["rate"]:
                best = {"ch_prime": chp, "rate": rate, "acc": acc}
        rows.append({"point": pi + 1, "module": k, "channels": ch,
                     "bits": bits, "base_acc": base_acc, **best})
    return rows


def accuracy_with_ae(model, backbone_params, ae, split_module, x, labels,
                     bits=8):
    feat = cnn_lib.forward(model, backbone_params, x, upto=split_module + 1)
    feat_hat = roundtrip(ae, feat, bits)
    logits = cnn_lib.forward_from(model, backbone_params, feat_hat,
                                  split_module + 1)
    return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
