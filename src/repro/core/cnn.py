"""The paper's CNN backbones (ResNet18 / VGG11 / MobileNetV2) in pure JAX,
organized as *modules* separated by the paper's partitioning points, with an
analytic per-module FLOPs/bytes walker used by the overhead model (Sec. 3.4
of the paper measures these on a Jetson Nano; we derive them from the same
module granularity — see core/overhead.py).

BatchNorm uses batch statistics (train-mode) throughout; running-stat
bookkeeping is irrelevant to the compression/scheduling experiments. The
statistics come in one pass (``_bn_stats``): a per-channel sum and sum of
squares in float32, variance E[x^2] - mean^2. Both sums fuse into the
convolution that makes the activation; the two-pass mean((x - mean)^2)
needs the mean first, so XLA gives it a second full read of every
activation.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ------------------------------------------------------------- primitives
# layer spec: ("conv", cin, cout, k, stride, pad) | ("dw", ch, k, stride)
# ("bn", ch) | ("relu",) | ("maxpool", k, s) | ("avgpool",) | ("fc", cin, cout)
# ("add", skip_marker)  -- handled inside blocks


def _conv_init(key, cin, cout, k):
    fan = cin * k * k
    w = jax.random.normal(key, (cout, cin, k, k)) * np.sqrt(2.0 / fan)
    return {"w": w}


def _conv(p, x, stride, pad, groups=1):
    return jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), [(pad, pad), (pad, pad)],
        feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _bn_init(ch):
    return {"scale": jnp.ones((ch,)), "bias": jnp.zeros((ch,))}


def _bn_stats(x):
    """Per-channel batch mean and biased variance of NCHW ``x`` from one
    sum and one sum of squares; the variance is clamped at 0 against
    rounding."""
    n = x.shape[0] * x.shape[2] * x.shape[3]
    mu = x.sum(axis=(0, 2, 3), keepdims=True) / n
    ms = jnp.square(x).sum(axis=(0, 2, 3), keepdims=True) / n
    return mu, jnp.maximum(ms - jnp.square(mu), 0.0)


def _bn(p, x, eps=1e-5):
    mu, var = _bn_stats(x)
    xn = (x - mu) * jax.lax.rsqrt(var + eps)
    return xn * p["scale"][None, :, None, None] + p["bias"][None, :, None, None]


# ------------------------------------------------------------- model defs
@dataclasses.dataclass
class CNNModel:
    name: str
    init: Callable                  # key -> params (list per module)
    run_module: Callable            # (params_i, i, x) -> x
    n_modules: int
    split_after: Tuple[int, ...]    # paper's 4 partitioning points (module idx)
    feature_shapes: Callable        # in_size -> list of (C,H,W) after each module
    module_flops: Callable          # in_size -> list of flops per module


# ------------------------------------------------------------------ resnet18
def _basic_block_init(key, cin, cout, stride):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"c1": _conv_init(k1, cin, cout, 3), "b1": _bn_init(cout),
         "c2": _conv_init(k2, cout, cout, 3), "b2": _bn_init(cout)}
    if stride != 1 or cin != cout:
        p["cd"] = _conv_init(k3, cin, cout, 1)
        p["bd"] = _bn_init(cout)
    return p


def _basic_block(p, x, stride):
    h = jax.nn.relu(_bn(p["b1"], _conv(p["c1"], x, stride, 1)))
    h = _bn(p["b2"], _conv(p["c2"], h, 1, 1))
    sc = x if "cd" not in p else _bn(p["bd"], _conv(p["cd"], x, stride, 0))
    return jax.nn.relu(h + sc)


def make_resnet18(num_classes=101, width=1.0):
    chs = [int(c * width) for c in (64, 64, 128, 256, 512)]

    def init(key):
        ks = jax.random.split(key, 12)
        mods = []
        mods.append({"c": _conv_init(ks[0], 3, chs[0], 7), "b": _bn_init(chs[0])})
        cin = chs[0]
        ki = 1
        for si, cout in enumerate(chs[1:]):
            blocks = []
            for bi in range(2):
                s = 2 if (si > 0 and bi == 0) else 1
                blocks.append(_basic_block_init(ks[ki], cin, cout, s))
                ki += 1
                cin = cout
            mods.append(blocks)
        wk = jax.random.split(ks[ki], 2)[0]
        mods.append({"w": jax.random.normal(wk, (cin, num_classes)) * 0.01,
                     "b": jnp.zeros((num_classes,))})
        return mods

    def run_module(p, i, x):
        if i == 0:
            x = jax.nn.relu(_bn(p["b"], _conv(p["c"], x, 2, 3)))
            return jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                [(0, 0), (0, 0), (1, 1), (1, 1)])
        if i == 5:
            x = x.mean(axis=(2, 3))
            return x @ p["w"] + p["b"]
        for bi, bp in enumerate(p):
            s = 2 if (i > 1 and bi == 0) else 1
            x = _basic_block(bp, x, s)
        return x

    def feature_shapes(in_size):
        s = in_size // 4
        shapes = [(chs[0], s, s)]
        for si, c in enumerate(chs[1:]):
            if si > 0:
                s = (s + 1) // 2
            shapes.append((c, s, s))
        shapes.append((num_classes,))
        return shapes

    def module_flops(in_size):
        fl = []
        s = in_size // 2
        fl.append(2 * 3 * chs[0] * 49 * s * s)          # stem conv
        s = in_size // 4
        cin = chs[0]
        for si, c in enumerate(chs[1:]):
            if si > 0:
                s = (s + 1) // 2
            f = 2 * cin * c * 9 * s * s + 2 * c * c * 9 * s * s
            if si > 0:
                f += 2 * cin * c * s * s
            f += 2 * c * c * 9 * s * s * 2 + 2 * c * c * 9 * s * s  # 2nd block
            fl.append(f)
            cin = c
        fl.append(2 * cin * num_classes)
        return fl

    return CNNModel("resnet18", init, run_module, 6, (1, 2, 3, 4),
                    feature_shapes, module_flops)


# -------------------------------------------------------------------- vgg11
_VGG = [64, "M", 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M"]


def make_vgg11(num_classes=101, width=1.0):
    cfgs = [int(c * width) if c != "M" else c for c in _VGG]
    # modules end after each of the first 4 maxpools; last module = rest+head
    bounds = [i + 1 for i, c in enumerate(cfgs) if c == "M"]
    mod_slices = ([slice(0, bounds[0])] +
                  [slice(bounds[i], bounds[i + 1]) for i in range(3)] +
                  [slice(bounds[3], len(cfgs))])

    def init(key):
        ks = jax.random.split(key, len(cfgs) + 1)
        mods = []
        cin = 3
        for sl in mod_slices:
            layers = []
            for j, c in enumerate(cfgs[sl]):
                if c == "M":
                    layers.append(("M", None))
                else:
                    layers.append(("C", {"c": _conv_init(ks[sl.start + j], cin, c, 3),
                                         "b": _bn_init(c)}))
                    cin = c
            mods.append(layers)
        mods.append({"w": jax.random.normal(ks[-1], (cin, num_classes)) * 0.01,
                     "b": jnp.zeros((num_classes,))})
        return mods

    def run_module(p, i, x):
        if i == 5:
            x = x.mean(axis=(2, 3))
            return x @ p["w"] + p["b"]
        for kind, lp in p:
            if kind == "M":
                x = jax.lax.reduce_window(
                    x, -jnp.inf, jax.lax.max, (1, 1, 2, 2), (1, 1, 2, 2),
                    [(0, 0)] * 4)
            else:
                x = jax.nn.relu(_bn(lp["b"], _conv(lp["c"], x, 1, 1)))
        return x

    def feature_shapes(in_size):
        shapes = []
        s, cin = in_size, 3
        for sl in mod_slices:
            for c in cfgs[sl]:
                if c == "M":
                    s //= 2
                else:
                    cin = c
            shapes.append((cin, s, s))
        shapes.append((num_classes,))
        return shapes

    def module_flops(in_size):
        fl = []
        s, cin = in_size, 3
        for sl in mod_slices:
            f = 0
            for c in cfgs[sl]:
                if c == "M":
                    s //= 2
                else:
                    f += 2 * cin * c * 9 * s * s
                    cin = c
            fl.append(f)
        fl.append(2 * cin * num_classes)
        return fl

    return CNNModel("vgg11", init, run_module, 6, (1, 2, 3, 4),
                    feature_shapes, module_flops)


# -------------------------------------------------------------- mobilenetv2
_MBV2 = [(1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
         (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1)]


def _inv_res_init(key, cin, cout, t, stride):
    k1, k2, k3 = jax.random.split(key, 3)
    mid = cin * t
    p = {}
    if t != 1:
        p["e"] = _conv_init(k1, cin, mid, 1)
        p["be"] = _bn_init(mid)
    p["d"] = {"w": jax.random.normal(k2, (mid, 1, 3, 3)) * np.sqrt(2.0 / 9)}
    p["bd"] = _bn_init(mid)
    p["p"] = _conv_init(k3, mid, cout, 1)
    p["bp"] = _bn_init(cout)
    return p


def _inv_res(p, x, cin, cout, t, stride):
    h = x
    if t != 1:
        h = jax.nn.relu6(_bn(p["be"], _conv(p["e"], h, 1, 0)))
    mid = cin * t
    h = jax.nn.relu6(_bn(p["bd"], _conv(p["d"], h, stride, 1, groups=mid)))
    h = _bn(p["bp"], _conv(p["p"], h, 1, 0))
    if stride == 1 and cin == cout:
        h = h + x
    return h


def make_mobilenetv2(num_classes=101, width=1.0):
    stages = [(t, int(c * width), n, s) for (t, c, n, s) in _MBV2]
    c_stem = int(32 * width)
    c_head = int(1280 * width)
    # modules: stem+stage1 | stage2 | stage3 | stage4+5 | stage6+7 | head
    groups = [[0], [1], [2], [3, 4], [5, 6]]

    def init(key):
        nblocks = sum(n for (_, _, n, _) in stages)
        ks = jax.random.split(key, nblocks + 3)
        mods = []
        cin = c_stem
        ki = 0
        first = {"c": _conv_init(ks[-1], 3, c_stem, 3), "b": _bn_init(c_stem)}
        for gi, g in enumerate(groups):
            blocks = [] if gi else [("stem", first)]
            for si in g:
                t, c, n, s = stages[si]
                for bi in range(n):
                    blocks.append((("blk", cin, c, t, s if bi == 0 else 1),
                                   _inv_res_init(ks[ki], cin, c, t,
                                                 s if bi == 0 else 1)))
                    ki += 1
                    cin = c
            mods.append(blocks)
        mods.append({"c": _conv_init(ks[-2], cin, c_head, 1),
                     "b": _bn_init(c_head),
                     "w": jax.random.normal(ks[-3], (c_head, num_classes)) * 0.01,
                     "bias": jnp.zeros((num_classes,))})
        return mods

    def run_module(p, i, x):
        if i == 5:
            x = jax.nn.relu6(_bn(p["b"], _conv(p["c"], x, 1, 0)))
            x = x.mean(axis=(2, 3))
            return x @ p["w"] + p["bias"]
        for item in p:
            if item[0] == "stem":
                x = jax.nn.relu6(_bn(item[1]["b"], _conv(item[1]["c"], x, 2, 1)))
            else:
                (_, cin, c, t, s), bp = item
                x = _inv_res(bp, x, cin, c, t, s)
        return x

    def feature_shapes(in_size):
        shapes = []
        s = in_size // 2
        cin = c_stem
        for g in groups:
            for si in g:
                t, c, n, st = stages[si]
                if st == 2:
                    s = (s + 1) // 2
                cin = c
            shapes.append((cin, s, s))
        shapes.append((num_classes,))
        return shapes

    def module_flops(in_size):
        fl = []
        s = in_size // 2
        f0 = 2 * 3 * c_stem * 9 * s * s
        cin = c_stem
        for gi, g in enumerate(groups):
            f = f0 if gi == 0 else 0
            f0 = 0
            for si in g:
                t, c, n, st = stages[si]
                for bi in range(n):
                    stride = st if bi == 0 else 1
                    mid = cin * t
                    if st == 2 and bi == 0:
                        s_out = (s + 1) // 2
                    else:
                        s_out = s
                    if t != 1:
                        f += 2 * cin * mid * s * s
                    f += 2 * mid * 9 * s_out * s_out
                    f += 2 * mid * c * s_out * s_out
                    s = s_out
                    cin = c
            fl.append(f)
        fl.append(2 * cin * c_head * s * s + 2 * c_head * num_classes)
        return fl

    return CNNModel("mobilenetv2", init, run_module, 6, (1, 2, 3, 4),
                    feature_shapes, module_flops)


CNN_FACTORY = {"resnet18": make_resnet18, "vgg11": make_vgg11,
               "mobilenetv2": make_mobilenetv2}


def forward(model: CNNModel, params, x, upto=None):
    """Run modules [0, upto) (None = all). x: (B, 3, H, W)."""
    return forward_from(model, params, x, 0, upto)


def forward_from(model: CNNModel, params, feat, start, stop=None):
    """Run modules [start, stop) (None = to the last) on ``feat``. Module
    ``i`` runs under the named scope ``module<i>``, which the device
    trace reads as the layer each op belongs to."""
    x = feat
    for i in range(start, model.n_modules if stop is None else stop):
        with jax.named_scope(f"module{i}"):
            x = model.run_module(params[i], i, x)
    return x
