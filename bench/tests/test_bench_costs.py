"""The operation and byte counts of ``bench/costs`` against hand counts."""
import json
import os

import pytest

from bench import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_resnet18_flops():
    c = harness.load_module(os.path.join(BENCH, "costs", "cnn_flops.py"))
    cfg = _config("resnet18-fleet1024")
    # stage 2 by hand, at 28^2: 3x3 64->128, 3x3 128->128 and the 1x1
    # shortcut 64->128 in block 1, two 3x3 128->128 in block 2
    stage2 = 2 * 784 * (64 * 128 * 9 + 3 * 128 * 128 * 9 + 64 * 128)
    mods = c.resnet18_modules(cfg)
    assert mods[2] == stage2 == 822_083_584
    # published: 1.82 GMAC for the whole net, so 3.64 GFLOP
    assert sum(mods) == pytest.approx(3.64e9, rel=0.01)
    # the edge half after split point 1 (module 1)
    assert c.flops_per_image(cfg, start=2) == pytest.approx(2.47e9, rel=0.01)


def test_mobilenetv2_flops():
    c = harness.load_module(os.path.join(BENCH, "costs", "cnn_flops.py"))
    cfg = _config("mobilenetv2-fleet1024")
    # published: 300 M multiply-adds at width 1.0 and 224x224
    assert sum(c.mobilenetv2_modules(cfg)) == pytest.approx(600e6, rel=0.01)
    assert c.flops_per_image(cfg, start=2) == pytest.approx(0.448e9,
                                                            rel=0.01)


def test_dequantize_cost():
    c = harness.load_module(os.path.join(BENCH, "costs", "dequantize.py"))
    n = 32 * 4 * 56 * 56
    assert c.cost(n) == {"flops": 2 * n, "bytes": 5 * n}
    assert c.cost(n, bits=16)["bytes"] == 6 * n
