"""MobileNetV2 (Sandler et al. 2018, arXiv:1801.04381, Table 2), written
from the paper, as the plain reference of the ``mobilenetv2-*``
configurations.

Stem 3x3/2 conv to 32 channels, then inverted-residual stages (t, c, n,
s) = (1,16,1,1) (6,24,2,2) (6,32,3,2) (6,64,4,2) (6,96,3,1) (6,160,3,2)
(6,320,1,1): a 1x1 expansion by t with BN and ReLU6 (left out where t is
1), a 3x3 depthwise conv of stride s with BN and ReLU6, a linear 1x1
projection with BN, and the identity added where the stride is 1 and the
widths agree. Head: 1x1 conv to 1280 with BN and ReLU6, global average
pool, the fully connected classifier (dropout is a training device and
left out). BatchNorm normalises with the batch's statistics, as the
system under test does.

Modules follow Hao et al. (arXiv:2205.11854) §VI's partitioning points:
stem and stage 1 | stage 2 | stage 3 | stages 4-5 | stages 6-7 | head.
``init`` draws parameters in the layout the program's ``run_module``
reads: each of modules 0-4 a list of ``(tag, params)`` items, the tag
``"stem"`` or ``("blk", cin, cout, t, stride)``; module 5 {"c", "b",
"w", "bias"}.
"""
from __future__ import annotations

import jax

from bench.reference import layers as L

MODULE_STAGES = ((0,), (1,), (2,), (3, 4), (5, 6))


def _stages(config):
    return [tuple(int(v) for v in s) for s in config["stages"]]


def init(key, config):
    stages = _stages(config)
    c_stem, c_head = int(config["stem_width"]), int(config["head_width"])
    keys = iter(jax.random.split(key, 256))
    mods, cin = [], c_stem
    for mi, group in enumerate(MODULE_STAGES):
        items = []
        if mi == 0:
            items.append(("stem", {
                "c": {"w": L.conv_weight(next(keys), c_stem, 3, 3)},
                "b": L.bn_params(next(keys), c_stem)}))
        for si in group:
            t, c, n, s = stages[si]
            for bi in range(n):
                stride = s if bi == 0 else 1
                mid = cin * t
                p = {}
                if t != 1:
                    p["e"] = {"w": L.conv_weight(next(keys), mid, cin, 1)}
                    p["be"] = L.bn_params(next(keys), mid)
                p["d"] = {"w": L.conv_weight(next(keys), mid, 1, 3)}
                p["bd"] = L.bn_params(next(keys), mid)
                p["p"] = {"w": L.conv_weight(next(keys), c, mid, 1)}
                p["bp"] = L.bn_params(next(keys), c)
                items.append((("blk", cin, c, t, stride), p))
                cin = c
        mods.append(items)
    fc = L.fc_params(next(keys), c_head, int(config["num_classes"]))
    mods.append({"c": {"w": L.conv_weight(next(keys), c_head, cin, 1)},
                 "b": L.bn_params(next(keys), c_head),
                 "w": fc["w"], "bias": fc["b"]})
    return mods


def _inverted_residual(ar, p, x, cin, cout, t, stride):
    h = x
    if t != 1:
        h = L.relu6(ar.batch_norm(ar.conv(h, p["e"]["w"], 1, 0),
                                  **p["be"]))
    h = L.relu6(ar.batch_norm(ar.conv(h, p["d"]["w"], stride, 1,
                                      groups=cin * t), **p["bd"]))
    h = ar.batch_norm(ar.conv(h, p["p"]["w"], 1, 0), **p["bp"])
    return h + x if stride == 1 and cin == cout else h


def module(ar, p, i, x):
    if i == 5:
        x = L.relu6(ar.batch_norm(ar.conv(x, p["c"]["w"], 1, 0), **p["b"]))
        return ar.dense(L.global_avg_pool(x), p["w"], p["bias"])
    for tag, bp in p:
        if tag == "stem":
            x = L.relu6(ar.batch_norm(ar.conv(x, bp["c"]["w"], 2, 1),
                                      **bp["b"]))
        else:
            _, cin, cout, t, stride = tag
            x = _inverted_residual(ar, bp, x, cin, cout, t, stride)
    return x


def forward(params, x, start=0, stop=6, ar=L.Arith()):
    """Modules [start, stop) on ``x``, computed as ``ar`` says."""
    x = ar.cast(x)
    for i in range(start, stop):
        x = module(ar, params[i], i, x)
    return x
