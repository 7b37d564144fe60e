"""The transformer edge's operation and byte counts (``bench/costs``)
against hand counts, and its scope readers on a synthetic trace."""
import json
import os
import types

import pytest

from bench import harness
from bench import layer_scopes as LS
from bench import trace as T

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(kind, name):
    return harness.load_module(os.path.join(BENCH, kind, name + ".py"))


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs",
                           "moonlight16b-a3b-edge.json")) as f:
        return json.load(f)


def test_moe_experts_cost(config):
    c = _load("costs", "moe_experts").cost(config, 16 * 1024)
    rows = 16 * 1024 * 6
    # gate, up and down: 2048 x 1408 multiply-adds each, per routed row
    assert c["flops"] == 2 * 3 * 2048 * 1408 * rows == 1_700_807_049_216
    # 64 experts' weights once in bfloat16: 1.11 GB a layer
    weights = 64 * 3 * 2048 * 1408 * 2
    assert weights == pytest.approx(1.107e9, rel=1e-3)
    assert c["bytes"] == weights + rows * 2048 * (2 + 4)


def test_moonlight_flops_per_token(config):
    m = _load("costs", "moonlight_flops")
    parts = m.moe_layer_flops(config, 1024)
    # the table of MFLOP per token and MoE layer at 1024 tokens
    assert parts["routed"] == 6 * 2 * 3 * 2048 * 1408 == 103_809_024
    assert parts["shared"] == 2 * 3 * 2048 * 2816 == 34_603_008
    assert parts["mla_proj"] == 2 * (2048 * 16 * 192 + 2048 * 576
                                     + 512 * 16 * 256 + 16 * 128 * 2048)
    assert parts["mla_proj"] == 27_525_120
    assert parts["attention"] == 2 * 16 * 320 * 1025 / 2
    assert parts["router"] == 2 * 2048 * 64
    assert sum(parts.values()) / 1e6 == pytest.approx(171.4, abs=0.05)
    # a batch of 16 x 1024: four layers, the decode, the head at 16
    # positions; 11.3 TFLOP, 57 ms at the v5e's bf16 peak
    step = m.edge_step_flops(config, 16, 1024)
    assert step == 16 * (1024 * (2 * 512 * 2048 + 4 * sum(parts.values()))
                         + 2 * 2048 * 163840)
    assert step / 197e12 == pytest.approx(0.0573, rel=0.01)


def _ctx():
    ops = {0: [("%mla.1", 0, 30), ("%route.2", 30, 40), ("%gmm.3", 40, 90),
               ("%swiglu.4", 90, 100), ("%head.5", 100, 110)]}
    scope_of = {
        "%mla.1": "jit(edge_step)/while/body/mla/dot_general:",
        "%route.2": "jit(edge_step)/while/body/moe_route/sort:",
        "%gmm.3": "ragged-dot-none",
        "%swiglu.4": "jit(edge_step)/while/body/moe_experts/mul:",
        "%head.5": "jit(edge_step)/lm_head/dot_general:"}
    return types.SimpleNamespace(
        trace=T.Trace(ops, [], scope_of=scope_of), window=(0, 100),
        info={"batches": 2, "batch": 16, "tokens": 1024, "edge_layers": 4},
        cell=types.SimpleNamespace(config=None), window_s=1e-7,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


@pytest.mark.parametrize("path,scope", [
    ("jit(edge_step)/while/body/mla/dot_general:", "mla"),
    ("jit(edge_step)/moe_route/jit(_where)/select_n:", "moe_route"),
    ("jit(edge_step)/moe_shared/dot_general:", "moe_shared"),
    ("ragged-dot-none", "moe_experts"),
    ("ragged-dot-metadata", "moe_experts"),
    ("jit(edge_step)/mla/ragged-dot", "mla"),
    ("jit(edge_step)/dequantize/pallas_call", None),
    ("jit(edge_step)/mlap/add:", None),
    ("", None), (None, None)])
def test_layer_scope_of_a_tf_op_path(path, scope):
    assert LS.scope_of_path(path) == scope


def test_per_batch_readers_and_roofline(config):
    ctx = _ctx()
    ms = {n: _load("metrics", n).read(ctx) for n in
          ("mla_ms_per_batch", "moe_route_ms_per_batch",
           "moe_experts_ms_per_batch")}
    assert ms == {"mla_ms_per_batch": 30 / 1e6 / 2,
                  "moe_route_ms_per_batch": 10 / 1e6 / 2,
                  "moe_experts_ms_per_batch": 60 / 1e6 / 2}
    ctx.cell.config = config
    ctx.cost = lambda name: _load("costs", name)
    c = _load("costs", "moe_experts").cost(config, 16 * 1024)
    least = max(c["flops"] / 197e12, c["bytes"] / 819e9)
    got = _load("metrics", "moe_experts_roofline").read(ctx)
    assert got == pytest.approx(100 * 2 * 4 * least / 60e-9)


def test_ragged_dot_without_a_path_counts_to_the_experts():
    """XLA's ``ragged-dot-…`` custom calls may carry no ``tf_op``: their
    own name puts them under ``moe_experts``."""
    ctx = _ctx()
    ctx.trace.ops[0].append(("ragged-dot-none.6", 100, 110))
    assert LS.op_ns(ctx, "moe_experts") == 60
    ctx.window = (0, 110)
    assert LS.op_ns(ctx, "moe_experts") == 70


def test_readers_are_silent_without_the_scopes():
    """A program that names none of these layers, as the parent of this
    cell's program, gives no reading, and raises nothing."""
    ctx = _ctx()
    ctx.trace.scope_of.clear()
    for name in ("mla_ms_per_batch", "moe_route_ms_per_batch",
                 "moe_experts_ms_per_batch", "moe_experts_roofline"):
        assert _load("metrics", name).read(ctx) is None
