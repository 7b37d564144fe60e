"""Latent attention and the dropless sigmoid-routed experts against the
plain reference (``bench/reference/moonlight.py``), at a small size on the
CPU with seeded random weights: Moonlight-16B-A3B's architecture at hidden
64, 4 heads, kv_lora_rank 32, RoPE 16, 8 experts top-2 with one shared,
vocabulary 512. The program runs the benchmark's layout of the same
weights (``bench/entries/llm_edge.py``)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from repro.configs import get_config
from repro.models import attention, model, moe
from repro.models.cache import entry_payload_bits, entry_shape

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "bench")
REF = harness.load_module(os.path.join(BENCH, "reference", "moonlight.py"))
ENTRY = harness.load_module(os.path.join(BENCH, "entries", "llm_edge.py"))
SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
         "intermediate_size": 128, "moe_intermediate_size": 32,
         "n_routed_experts": 8, "num_experts_per_tok": 2,
         "n_shared_experts": 1, "num_hidden_layers": 3, "vocab_size": 512}
B, T = 2, 24
# Program and reference take the same bfloat16 inputs to every product and
# accumulate in float32; they differ in summation order (float32, ~1e-7)
# and where that flips a bfloat16 rounding of a product's input (~4e-3 of
# one element of 64). 1e-2 of the reference's length leaves room for a
# few such flips and none for a missing or altered term.
TOL = 1e-2


@pytest.fixture(scope="module")
def small():
    with open(os.path.join(BENCH, "configs", "moonlight16b-a3b-edge.json")) as f:
        c = json.load(f)
    c.update(SMALL)
    cfg = ENTRY.program_config(c)
    ue = REF.init_ue(jax.random.PRNGKey(0), c)
    edge = REF.init_edge(jax.random.PRNGKey(1), c)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, 512)
    return c, cfg, ue, edge, tokens


def _gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _full_params(ue, edge):
    """The program's whole-model tree: the reference's UE weights as its
    embedding and lead layer, the edge weights as its stacked layers."""
    p = ue["dense"][0]
    lead = {"ln1": {"scale": p["attn_norm"]},
            "attn": {"wq": p["q_proj"], "wkv_a": p["kv_a_proj"],
                     "kv_norm": p["kv_a_norm"], "wkv_b": p["kv_b_proj"],
                     "wo": p["o_proj"]},
            "ln2": {"scale": p["ffn_norm"]},
            "mlp": {"wg": p["gate"], "wi": p["up"], "wo": p["down"]}}
    params = ENTRY.program_params(edge)
    params["decoder"]["lead"] = (lead,)
    params["embed"] = ue["embed"]
    return params


def test_edge_matches_reference(small):
    """The edge entry's logits and every layer's latent cache against the
    reference's, from the same hidden states."""
    c, cfg, ue, edge, tokens = small
    x = REF.ue_forward(ue, tokens, c)
    logits, cache = jax.jit(lambda p, x: model.edge_forward(
        ENTRY.program_params(p), cfg, x))(edge, x)
    want, ckv, _ = REF.edge_forward(edge, x, c)
    assert logits.shape == (B, 512) and logits.dtype == jnp.float32
    assert _gap(logits, want) < TOL
    got = cache["blocks"][0]["ckv"]
    assert got.shape == ckv.shape == (2, B, T, 32)
    assert _gap(got, ckv) < TOL
    assert _gap(got[0], ckv[0]) < 1e-5      # before any routing
    assert cache["blocks"][0]["krope"].shape == (2, B, T, 16)


def test_ue_then_edge_is_the_full_forward(small):
    """With the codec bypassed, the UE half then the edge half is the
    whole model's forward, last position."""
    c, cfg, ue, edge, tokens = small
    params = _full_params(ue, edge)
    full, _, _ = model.apply_model(params, cfg, tokens, mode="train")
    ue_p, edge_p = model.split_params(params, cfg, 1)
    assert set(ue_p) == {"embed", "decoder"} and "embed" not in edge_p
    x = model.ue_forward(ue_p, cfg, tokens)
    assert _gap(x, REF.ue_forward(ue, tokens, c)) < TOL
    logits, _ = model.edge_forward(edge_p, cfg, x)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full[:, -1]), rtol=0, atol=1e-5)


def test_forced_imbalance_stays_dropless(small):
    """A correction bias that puts one expert in every token's top-2: it
    takes every token, and the layer still gives the reference's answer."""
    c, cfg, _, edge, _ = small
    edge = dict(edge, moe=dict(edge["moe"], bias=edge["moe"]["bias"].at[
        :, 3].set(100.0)))
    first = lambda tree: jax.tree_util.tree_map(lambda a: a[0], tree)
    p = first(edge["moe"])
    pm = first(ENTRY.program_params(edge)["decoder"]["blocks"][0]["moe"])
    h = jax.random.normal(jax.random.PRNGKey(5), (B, T, 64))
    idx, _, _ = moe.route(pm, h.reshape(-1, 64), cfg.moe)
    assert bool(jnp.all(jnp.any(idx == 3, axis=1)))
    got, _ = moe.apply_moe(pm, h, cfg)
    want, _ = REF.moe(p, h.reshape(-1, 64), c, REF.Arith())
    assert _gap(got.reshape(-1, 64), want) < TOL


def test_bias_chooses_but_does_not_weight(small):
    """The correction bias moves some choices; a chosen expert's weight is
    its sigmoid score over the chosen scores' sum, whatever the bias."""
    _, cfg, _, edge, _ = small
    m = ENTRY.program_params(edge)["decoder"]["blocks"][0]["moe"]
    pm = jax.tree_util.tree_map(lambda a: a[0], m)
    pm["router_bias"] = pm["router_bias"] * 10
    h = jax.random.normal(jax.random.PRNGKey(6), (64, 64))
    idx, w, scores = moe.route(pm, h, cfg.moe)
    idx0, w0, scores0 = moe.route(
        dict(pm, router_bias=jnp.zeros_like(pm["router_bias"])), h, cfg.moe)
    np.testing.assert_array_equal(np.asarray(scores), np.asarray(scores0))
    same = np.all(np.sort(idx, 1) == np.sort(idx0, 1), axis=1)
    assert 0 < same.sum() < len(same)
    chosen = jnp.take_along_axis(scores, idx, axis=1)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(chosen / chosen.sum(1, keepdims=True)
                                  * cfg.moe.routed_scaling), rtol=1e-6)
    order = lambda i, v: np.take_along_axis(np.asarray(v), np.argsort(
        np.asarray(i), 1), 1)
    np.testing.assert_allclose(order(idx, w)[same], order(idx0, w0)[same],
                               rtol=1e-6)


def test_latent_cache_entry_is_576_values_a_token():
    """Moonlight's cache entry per layer is the latent and the shared RoPE
    key (512 + 64 values a token), not full keys and values of 16 heads."""
    cfg = get_config("moonlight-16b-a3b")
    for btype in ("dense", "moe"):
        shape = entry_shape(cfg, btype, 2, 100)
        assert set(shape) == {"ckv", "krope", "pos"}
        values = sum(int(np.prod(s)) for n, (s, _) in shape.items()
                     if n != "pos")
        assert values == 2 * 100 * 576
    bits = entry_payload_bits(cfg, "moe", 1, 100)
    assert bits == 100 * (576 * 32 + 32)        # float32 latent, int32 pos
    gqa = 2 * 100 * cfg.n_heads * (128 + 64) * 32
    assert bits < gqa / 10


def test_mla_decode_matches_prefill(small):
    """A token decoded against the latent cache (query-absorbed form) gives
    the full-sequence attention's output at its position."""
    c, cfg, _, edge, _ = small
    pa = ENTRY.program_params(edge)["decoder"]["blocks"][0]["attn"]
    pa = jax.tree_util.tree_map(lambda a: a[0].astype(jnp.float32), pa)
    cfg32 = cfg.replace(param_dtype="float32")
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, 64))
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    full, (c_kv, k_rope) = attention.mla_attention(pa, x, cfg32, pos)
    from repro.models.cache import pack_latent
    cache = pack_latent(c_kv[:, :-1], k_rope[:, :-1], pos[:, :-1], T,
                        jnp.float32)
    out, cache2 = attention.mla_decode(pa, x[:, -1:], cfg32, pos[:, -1:],
                                       cache, T - 1)
    assert _gap(out[:, 0], full[:, -1]) < 1e-4
    assert int(cache2["pos"][0, -1]) == T - 1
