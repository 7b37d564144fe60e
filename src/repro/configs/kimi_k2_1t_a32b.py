"""kimi-k2-1t-a32b [trillion-param MoE; hf:moonshotai/Kimi-K2-Instruct].

61 layers, d_model 7168, 64 heads of multi-head latent attention
(q_lora_rank 1536, kv_lora_rank 512, nope 128 + rope 64 query/key dims,
value 128), one leading dense layer of width 18432, then 60 MoE layers of
384 routed experts (width 2048, 8 per token) and one shared expert,
routed by sigmoid scores with a correction bias (``noaux_tc``, one
group), top-8 weights normalised and scaled by 2.827; dropless. Vocab
163840, untied head, rms_norm_eps 1e-6. Left out: the published YaRN
rope scaling (factor 32 over 4096 positions); RoPE here is plain with
theta 50000. XL config: FSDP param sharding and Adafactor states (AdamW
f32 states for 1T params cannot fit the assigned meshes).
"""
from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="kimi-k2-1t-a32b",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=64,
    n_kv_heads=64,
    d_ff=18432,
    vocab_size=163840,
    rope_theta=50000.0,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    first_dense_layers=1,
    block_pattern=("moe",),
    moe=MoEConfig(n_experts=384, top_k=8, d_expert=2048, n_shared_experts=1,
                  routed_scaling=2.827, dropless=True),
    fsdp=True,
    optimizer="adafactor",
)
