"""moonlight-16b-a3b [MoE with latent attention; hf:moonshotai/Moonlight-16B-A3B].

Kimi K2's architecture (``model_type`` deepseek_v3) at widths where one
chip holds whole expert layers: 27 layers, d_model 2048, 16 heads of
multi-head latent attention (no q_lora; kv_lora_rank 512, nope 128 +
rope 64 query/key dims, value 128), one leading dense layer of width
11264, then 26 MoE layers of 64 routed experts (width 1408, 6 per token)
and 2 shared experts, routed by sigmoid scores with a correction bias
(``noaux_tc``, one group), top-6 weights normalised and scaled by 2.446;
dropless. rope_theta 50000 without scaling, rms_norm_eps 1e-5, vocab
163840, untied head.

Parameters are bfloat16; the residual stream, norms, router and softmax
run in float32 and every matrix product takes bfloat16 inputs with
float32 accumulation (``compute_dtype`` is the residual stream's).
"""
from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    vocab_size=163840,
    norm_eps=1e-5,
    rope_theta=50000.0,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    first_dense_layers=1,
    block_pattern=("moe",),
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared_experts=2,
                  routed_scaling=2.446, dropless=True),
    compute_dtype="float32",
)
