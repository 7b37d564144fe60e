"""ResNet-18 (He et al. 2016, arXiv:1512.03385, Table 1), written from
the paper, as the plain reference of the ``resnet18-*`` configurations.

Modules follow Hao et al. (arXiv:2205.11854) §VI's partitioning points:
0 stem (7x7/2 conv, BN, ReLU, 3x3/2 max pool), 1-4 the four stages of two
basic blocks each (64/128/256/512 channels, stride 2 and a 1x1 projection
shortcut at the start of stages 2-4), 5 global average pool and the fully
connected classifier. BatchNorm normalises with the batch's statistics,
as the system under test does; a deployed model would use running
statistics instead.

``init`` draws the parameters from a key in the layout the program's
``run_module`` reads: module 0 {"c": {"w"}, "b": {"scale", "bias"}},
modules 1-4 a list of blocks {"c1", "b1", "c2", "b2"[, "cd", "bd"]},
module 5 {"w", "b"}.
"""
from __future__ import annotations

import jax

from bench.reference import layers as L

STAGE_STRIDES = (1, 2, 2, 2)


def _widths(config):
    return [int(config["stem_width"])] + [int(c) for c in config["widths"]]


def init(key, config):
    chs = _widths(config)
    keys = iter(jax.random.split(key, 64))
    mods = [{"c": {"w": L.conv_weight(next(keys), chs[0], 3, 7)},
             "b": L.bn_params(next(keys), chs[0])}]
    cin = chs[0]
    for si, cout in enumerate(chs[1:]):
        blocks = []
        for bi in range(int(config["blocks"][si])):
            stride = STAGE_STRIDES[si] if bi == 0 else 1
            p = {"c1": {"w": L.conv_weight(next(keys), cout, cin, 3)},
                 "b1": L.bn_params(next(keys), cout),
                 "c2": {"w": L.conv_weight(next(keys), cout, cout, 3)},
                 "b2": L.bn_params(next(keys), cout)}
            if stride != 1 or cin != cout:
                p["cd"] = {"w": L.conv_weight(next(keys), cout, cin, 1)}
                p["bd"] = L.bn_params(next(keys), cout)
            blocks.append(p)
            cin = cout
        mods.append(blocks)
    mods.append(L.fc_params(next(keys), cin, int(config["num_classes"])))
    return mods


def _block(ar, p, x, stride):
    h = L.relu(ar.batch_norm(ar.conv(x, p["c1"]["w"], stride, 1),
                             **p["b1"]))
    h = ar.batch_norm(ar.conv(h, p["c2"]["w"], 1, 1), **p["b2"])
    sc = x if "cd" not in p else ar.batch_norm(
        ar.conv(x, p["cd"]["w"], stride, 0), **p["bd"])
    return L.relu(h + sc)


def module(ar, p, i, x):
    if i == 0:
        x = L.relu(ar.batch_norm(ar.conv(x, p["c"]["w"], 2, 3), **p["b"]))
        return L.max_pool_3x3_s2(x)
    if i == 5:
        return ar.dense(L.global_avg_pool(x), p["w"], p["b"])
    for bi, bp in enumerate(p):
        x = _block(ar, bp, x, STAGE_STRIDES[i - 1] if bi == 0 else 1)
    return x


def forward(params, x, start=0, stop=6, ar=L.Arith()):
    """Modules [start, stop) on ``x``, computed as ``ar`` says."""
    x = ar.cast(x)
    for i in range(start, stop):
        x = module(ar, params[i], i, x)
    return x
