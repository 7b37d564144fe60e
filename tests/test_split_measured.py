"""Measured split tables (compiled-HLO CNN costs, LLM-decode KV-payload
tables), context-rung fleets, and the quantizer round-trip bound."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import overhead as oh
from repro.core.compressor import dequantize, quantize
from repro.core.cnn import make_resnet18
from repro.core.fleets import LLM_CTX_RUNGS, make_llm_mixed_fleet
from repro.core.split import (llm_decode_split_table,
                              measured_cnn_module_costs,
                              measured_cnn_split_table, measured_split_table)
from repro.models.cache import entry_payload_bits


# ------------------------------------------------------ LLM decode tables
def test_llm_decode_table_invariants():
    cfg = get_config("qwen3-1.7b")
    plan = llm_decode_split_table(cfg, 256, gen_tokens=8, kv_bits=8)
    assert plan.name == "qwen3-1.7b-decode-ctx256"
    assert plan.n_actions == len(plan.points) + 2
    # _finalize contract: free raw offload, monotone UE compute, silent local
    assert plan.t_local[0] == 0.0
    assert np.all(np.diff(plan.t_local[1:-1]) >= -1e-9)
    assert plan.f_bits[-1] == 0.0
    # a 1.7b stack fits a phone NPU at every split depth
    assert plan.feasible.all()
    # KV cache dominates the boundary payload and accumulates with depth
    assert np.all(np.diff(plan.f_bits[1:-1]) > 0)
    # full-local covers prefill + decode: strictly more compute than the
    # deepest split's prefill-only share
    assert plan.t_local[-1] > plan.t_local[-2]


def test_llm_payload_monotone_in_context():
    """f_bits at every split point is a nondecreasing function of context
    length — the property that makes long-context offloading expensive."""
    cfg = get_config("qwen3-1.7b")
    plans = [llm_decode_split_table(cfg, c, gen_tokens=8, kv_bits=8)
             for c in (256, 1024, 4096)]
    for a, b in zip(plans, plans[1:]):
        assert np.all(b.f_bits[:-1] > a.f_bits[:-1])
        # and so is the prefill compute at each split
        assert np.all(b.t_local[1:] > a.t_local[1:])


def test_llm_table_memory_gate():
    """A 9B recurrent stack does NOT fit a phone NPU at deep splits: the
    per-layer param-bytes feasibility gate must trip, while raw offload
    (b=0, no UE-side layers) stays feasible. Also exercises the rec /
    sliding-window payload branches of entry_payload_bits."""
    cfg = get_config("recurrentgemma-9b")
    plan = llm_decode_split_table(cfg, 1024, gen_tokens=8)
    assert bool(plan.feasible[0])
    assert not plan.feasible.all()
    assert not bool(plan.feasible[-1])     # 9B params >> 8 GB phone


def test_entry_payload_bits_window_cap_and_rec_state():
    cfg = get_config("recurrentgemma-9b")
    btypes = cfg.block_types()
    assert "rec" in btypes and "lattn" in btypes
    # rec state is O(1) in context
    assert entry_payload_bits(cfg, "rec", 1, 64) \
        == entry_payload_bits(cfg, "rec", 1, 4096)
    # sliding-window KV grows until the window fills, then caps
    w = cfg.window
    small = entry_payload_bits(cfg, "lattn", 1, w // 4)
    at_w = entry_payload_bits(cfg, "lattn", 1, w)
    beyond = entry_payload_bits(cfg, "lattn", 1, 4 * w)
    assert small < at_w == beyond
    with pytest.raises(ValueError):
        entry_payload_bits(cfg, "lattn", 1, 0)


def test_entry_payload_bits_kv_quant():
    """int8 codes + f32 per-(slot, head) scales vs bf16: quantized cache
    payload must be strictly smaller, and match the hand sum."""
    cfg = get_config("qwen3-1.7b")
    full = entry_payload_bits(cfg, "attn", 1, 512)
    cfg8 = cfg.replace(kv_quant_bits=8)
    quant = entry_payload_bits(cfg8, "attn", 1, 512)
    assert quant < full
    hkv, dh, lc = cfg.n_kv_heads, cfg.head_dim, 512
    expect = (2 * lc * hkv * dh * 8        # int8 k+v codes
              + 2 * lc * hkv * 32          # f32 scales
              + lc * 32)                   # int32 pos
    assert quant == expect


def test_measured_split_table_dispatch():
    cfg = get_config("qwen3-1.7b")
    plan = measured_split_table(cfg, ctx_len=256, gen_tokens=8)
    assert plan.name.endswith("-decode-ctx256")


# -------------------------------------------------- measured CNN tables
@pytest.fixture(scope="module")
def tiny_cnn():
    return make_resnet18(10, width=0.25)


@pytest.fixture(scope="module")
def tiny_costs(tiny_cnn):
    return measured_cnn_module_costs(tiny_cnn, 32)


def test_measured_cnn_costs_vs_walker(tiny_cnn, tiny_costs):
    """XLA's compiled cost analysis vs the hand-derived conv walker: the
    walker ignores BN/elementwise and XLA folds/pads, so only loose
    cumulative agreement is expected — same order of magnitude, every
    module nonzero."""
    assert len(tiny_costs) == tiny_cnn.n_modules
    meas = np.array([c["flops"] for c in tiny_costs], float)
    walk = np.array(tiny_cnn.module_flops(32), float)
    assert (meas > 0).all() and (np.array(
        [c["bytes_accessed"] for c in tiny_costs]) > 0).all()
    ratio = meas.sum() / walk.sum()
    assert 0.25 < ratio < 4.0


def test_measured_cnn_split_table(tiny_cnn, tiny_costs):
    plan = measured_cnn_split_table(tiny_cnn, 32, module_costs=tiny_costs)
    assert plan.name.endswith("-measured")
    assert plan.t_local[0] == 0.0
    assert np.all(np.diff(plan.t_local[1:-1]) >= -1e-9)
    assert plan.f_bits[-1] == 0.0
    assert plan.feasible.all()
    # CNN payloads SHRINK with depth past the early blow-up — the last
    # split point ships far fewer bits than raw input
    assert plan.f_bits[len(plan.points)] < plan.f_bits[0]


def test_measured_cnn_rd_override(tiny_cnn, tiny_costs):
    """Measured rate-distortion rows replace the paper's ae_ratio
    constants: f_bits must reflect each row's (ch_prime, bits)."""
    model, costs = tiny_cnn, tiny_costs
    shapes = model.feature_shapes(32)
    rd = [{"ch_prime": 2, "bits": 6} for _ in model.split_after]
    plan = measured_cnn_split_table(model, 32, module_costs=costs, rd=rd)
    for pi, k in enumerate(model.split_after):
        _, h, w = shapes[k]
        assert plan.f_bits[pi + 1] == 2 * h * w * 6
    with pytest.raises(ValueError):
        measured_cnn_split_table(model, 32, module_costs=costs, rd=rd[:-1])


# ------------------------------------------------------ mixed fleets
def test_make_llm_mixed_fleet():
    fleet = make_llm_mixed_fleet(n_cnn=2, gen_tokens=8)
    n = 2 + len(LLM_CTX_RUNGS)
    assert fleet.t_local.shape[0] == n
    assert fleet.names[:2] == ["resnet18", "resnet18"]
    assert [f"qwen3-1.7b-decode-ctx{c}" for c in LLM_CTX_RUNGS] \
        == fleet.names[2:]
    # full-local lives in the LAST padded slot for every UE: feasible,
    # zero payload, and the longest rung is the slowest local run
    assert fleet.feasible[:, -1].all()
    assert np.all(fleet.f_bits[:, -1] == 0.0)
    llm_local = fleet.t_local[2:, -1]
    assert np.all(np.diff(llm_local) > 0)


# ------------------------------------------------------ quantizer bound
@pytest.mark.parametrize("bits", [4, 8])
def test_quantize_roundtrip_error_bound(bits):
    x = jax.random.normal(jax.random.PRNGKey(0), (256,)) * 3.0
    codes, minv, maxv = quantize(x, bits)
    back = dequantize(codes, bits, minv, maxv)
    step = (maxv - minv) / ((1 << bits) - 1)
    # round-to-nearest on a uniform grid: error <= half a step everywhere
    # (bound stated as one full step to absorb float32 rounding)
    assert float(jnp.max(jnp.abs(back - x))) <= float(step)


def test_moonlight_payload_prices_the_latent():
    """Moonlight split after its dense front layer: the boundary feature at
    ratio 4 and 8 bits is 512 B a token, and the UE-side cache shipped with
    it is layer 0's latent (c_kv and the shared RoPE key)."""
    from repro.core.split import transformer_split_table
    cfg = get_config("moonlight-16b-a3b")
    ctx = 1024
    # 26 evenly spaced points over 27 layers: the first falls after layer 0
    prefill = transformer_split_table(cfg, seq_len=ctx, ae_ratio=4,
                                      quant_bits=8, n_points=26)
    assert prefill.points[0] == 1
    assert prefill.f_bits[1] / ctx / 8 == 512
    plan = llm_decode_split_table(cfg, ctx, ae_ratio=4, quant_bits=8,
                                  n_points=26)
    assert plan.points[0] == 1
    latent = entry_payload_bits(cfg, cfg.block_types()[0], 1, ctx)
    assert latent == ctx * (512 + 64 + 1) * 32
    assert plan.f_bits[1] == ctx * 512 * 8 + latent
