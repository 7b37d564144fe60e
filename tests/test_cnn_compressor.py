"""CNN backbones + autoencoder compressor training (paper §2, §6.1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cnn as cnn_lib
from repro.core.compressor import (accuracy_with_ae, init_autoencoder,
                                   pca_init_autoencoder, roundtrip,
                                   train_autoencoder)
from repro.data.synthetic import synthetic_image_batch


@pytest.mark.parametrize("name", ["resnet18", "vgg11", "mobilenetv2"])
def test_cnn_forward_shapes(name):
    model = cnn_lib.CNN_FACTORY[name](num_classes=11, width=0.25)
    params = model.init(jax.random.PRNGKey(0))
    x = jnp.ones((2, 3, 32, 32))
    y = cnn_lib.forward(model, params, x)
    assert y.shape == (2, 11)
    assert bool(jnp.all(jnp.isfinite(y)))


@pytest.mark.parametrize("name", ["resnet18", "vgg11", "mobilenetv2"])
def test_cnn_split_equals_full(name):
    """forward == forward_from(forward(..., upto)) at every split point."""
    model = cnn_lib.CNN_FACTORY[name](num_classes=7, width=0.25)
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 32, 32))
    y_full = cnn_lib.forward(model, params, x)
    for k in model.split_after:
        feat = cnn_lib.forward(model, params, x, upto=k + 1)
        y_split = cnn_lib.forward_from(model, params, feat, k + 1)
        np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_split),
                                   rtol=1e-4, atol=1e-4)


def test_feature_shape_walker_matches_runtime():
    model = cnn_lib.make_resnet18(num_classes=7)
    params = model.init(jax.random.PRNGKey(0))
    x = jnp.ones((1, 3, 64, 64))
    shapes = model.feature_shapes(64)
    for k in model.split_after:
        feat = cnn_lib.forward(model, params, x, upto=k + 1)
        assert tuple(feat.shape[1:]) == tuple(shapes[k]), (k, feat.shape)


def _two_pass_bn_stats(x):
    """BatchNorm's statistics as the mean, then mean((x - mean)^2)."""
    mu = x.mean(axis=(0, 2, 3), keepdims=True)
    return mu, jnp.mean(jnp.square(x - mu), axis=(0, 2, 3), keepdims=True)


@pytest.mark.parametrize("offset", [0.0, 1.0, 3.0, 10.0])
def test_bn_stats_match_two_pass(offset):
    """One-pass statistics (sum and sum of squares) give the two-pass
    variance and the same normalized activations, on channels whose means
    lie ``offset`` standard deviations from 0 and whose scales span
    e^-2..e^2. E[x^2] - mean^2 cancels (1 + offset^2)-fold, so float32
    rounding of the sums reaches the variance by that factor: rtol 1e-5
    times it, 1e-4 at 3 standard deviations, about 1e-3 at 10."""
    kx, ks = jax.random.split(jax.random.PRNGKey(0))
    std = jnp.exp(jnp.clip(jax.random.normal(ks, (1, 16, 1, 1)), -2, 2))
    x = (jax.random.normal(kx, (8, 16, 14, 14)) + offset) * std
    tol = 1e-5 * (1 + offset ** 2)
    mu, var = cnn_lib._bn_stats(x)
    _, var2 = _two_pass_bn_stats(x)
    np.testing.assert_allclose(np.asarray(mu / std), offset, atol=0.15)
    np.testing.assert_allclose(np.asarray(var), np.asarray(var2), rtol=tol)
    p = {"scale": jnp.ones((16,)), "bias": jnp.zeros((16,))}
    want = (x - x.mean(axis=(0, 2, 3), keepdims=True)) * jax.lax.rsqrt(
        var2 + 1e-5)
    np.testing.assert_allclose(np.asarray(cnn_lib._bn(p, x)),
                               np.asarray(want), rtol=tol, atol=tol)


@pytest.mark.parametrize("value", [3.7, 123.4])
def test_bn_stats_constant_channel(value):
    """A constant channel, whose E[x^2] - mean^2 rounds below 0 in float32
    at these values, gets a variance clamped to a finite 0 or more, and
    BatchNorm stays finite."""
    x = jnp.concatenate([jnp.full((4, 1, 8, 8), value),
                         jax.random.normal(jax.random.PRNGKey(0),
                                           (4, 2, 8, 8))], axis=1)
    _, var = cnn_lib._bn_stats(x)
    assert bool(jnp.all(jnp.isfinite(var))) and float(var.min()) >= 0.0
    p = {"scale": jnp.ones((3,)), "bias": jnp.zeros((3,))}
    assert bool(jnp.all(jnp.isfinite(cnn_lib._bn(p, x))))


@pytest.mark.parametrize("name", ["resnet18", "vgg11", "mobilenetv2"])
def test_cnn_logits_match_two_pass_bn(name, monkeypatch):
    """The full network as the benchmark runs it (published widths,
    224x224), batch 4: logits under the one-pass statistics lie within
    1e-4 of the two-pass form's, per image, as a distance over the
    logits' length."""
    model = cnn_lib.CNN_FACTORY[name](num_classes=11)
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 3, 224, 224))
    y = jax.jit(lambda x: cnn_lib.forward(model, params, x))(x)
    monkeypatch.setattr(cnn_lib, "_bn_stats", _two_pass_bn_stats)
    y2 = jax.jit(lambda x: cnn_lib.forward(model, params, x))(x)
    gap = jnp.linalg.norm(y - y2, axis=-1) / jnp.linalg.norm(y2, axis=-1)
    assert float(gap.max()) < 1e-4, gap


def test_ae_training_reduces_loss():
    model = cnn_lib.make_resnet18(num_classes=5, width=0.25)
    params = model.init(jax.random.PRNGKey(0))

    def data_iter():
        k = 0
        while True:
            x, y = synthetic_image_batch(jax.random.PRNGKey(k), 8, 32,
                                         n_classes=5)
            yield x, y
            k += 1

    split = model.split_after[0]
    ch = model.feature_shapes(32)[split][0]
    ae, _, logs = train_autoencoder(
        jax.random.PRNGKey(1), model, params, split, data_iter(),
        ch=ch, ch_prime=max(1, ch // 4), steps=25, lr=1e-3)
    first = np.mean([l["l2"] for l in logs[:5]])
    last = np.mean([l["l2"] for l in logs[-5:]])
    assert last < first


def test_pca_init_3d_matches_4d():
    """pca_init_autoencoder treats (B, C, H, W) CNN features and their
    channel-last (B, H*W, C) flattening as the SAME sample set — both
    layouts must produce identical principal components."""
    feats4 = jax.random.normal(jax.random.PRNGKey(0), (3, 8, 4, 4))
    b, c, h, w = feats4.shape
    feats3 = jnp.moveaxis(feats4, 1, -1).reshape(b, h * w, c)
    ae4 = pca_init_autoencoder(feats4, 3)
    ae3 = pca_init_autoencoder(feats3, 3)
    np.testing.assert_allclose(np.asarray(ae4["enc"]),
                               np.asarray(ae3["enc"]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ae4["dec"]),
                               np.asarray(ae3["dec"]), rtol=1e-5, atol=1e-6)
    # the components actually compress: PCA reconstruction beats a random
    # linear AE of the same width on the features it was fit to
    rand = init_autoencoder(jax.random.PRNGKey(1), c, 3)
    err_pca = float(jnp.mean((roundtrip(ae4, feats4) - feats4) ** 2))
    err_rand = float(jnp.mean((roundtrip(rand, feats4) - feats4) ** 2))
    assert err_pca < err_rand


def test_ae_quantized_roundtrip_close():
    ae = init_autoencoder(jax.random.PRNGKey(0), 16, 16)
    # near-orthogonal init at same width won't be identity, but roundtrip
    # must at least be finite and the quantized path close to unquantized
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 4, 4))
    r_f = roundtrip(ae, x, bits=None)
    r_q = roundtrip(ae, x, bits=8)
    assert float(jnp.max(jnp.abs(r_f - r_q))) < 0.1 * float(
        jnp.max(jnp.abs(r_f)) + 1)
