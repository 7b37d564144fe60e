"""Model/config system for the repro framework.

Every assigned architecture is expressed as a ``ModelConfig``. Block layout is
given by ``block_pattern`` (repeated cyclically over ``n_layers``), which lets
one assembly routine cover dense, MoE, SSM, hybrid (RG-LRU + local attention),
encoder-decoder (audio) and cross-attention (VLM) families while keeping the
compiled HLO depth-independent (scan over stacked per-pattern-group params).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int            # per-expert FFN hidden dim
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # DeepSeek-V3's layer: the ``noaux_tc`` router with one group (sigmoid
    # scores, a per-expert correction bias that chooses the experts but
    # does not weight them, top-k weights normalised and scaled by
    # ``routed_scaling``), and every token reaches all top_k experts (sort
    # + grouped matmul). False is the GShard layer: softmax router and a
    # capacity dispatch that drops tokens past ``capacity_factor``.
    dropless: bool = False
    routed_scaling: float = 1.0        # dropless only


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128       # N
    d_conv: int = 4
    expand: int = 2          # d_inner = expand * d_model
    head_dim: int = 64       # P;  n_heads = d_inner // head_dim
    chunk: int = 256         # SSD chunk length
    n_groups: int = 1        # B/C groups


@dataclass(frozen=True)
class EncoderConfig:
    """Encoder stack for enc-dec (audio) archs. Frontend is stubbed: the
    encoder consumes precomputed frame embeddings (see input_specs)."""
    n_layers: int = 24
    n_frames: int = 1024     # stub frontend output length
    d_frontend: int = 0      # 0 => frames already at d_model


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str              # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    d_head: int = 0          # 0 => d_model // n_heads
    norm: str = "rmsnorm"    # rmsnorm | layernorm
    act: str = "swiglu"      # swiglu | gelu
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    # Multi-head latent attention (DeepSeek-V2/V3): kv_lora_rank > 0 turns
    # it on. Keys and values come from a per-token latent c_kv of
    # kv_lora_rank values (RMS-normed) plus one shared RoPE key of
    # qk_rope_head_dim; the cache holds those two. q_lora_rank > 0 adds
    # the query's own low-rank path.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # leading dense layers ("dense" blocks of width d_ff) before the
    # block_pattern starts (DeepSeek's first_k_dense_replace)
    first_dense_layers: int = 0
    # Block layout. Entries: "dense" (attn+mlp), "moe" (attn+moe),
    # "mamba2", "rec" (RG-LRU+mlp), "lattn" (local attn+mlp),
    # "xattn" (cross-attn+mlp, VLM), "decx" (self+cross, enc-dec decoder).
    block_pattern: Tuple[str, ...] = ("dense",)
    window: int = 0          # local-attention window (hybrid archs)
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    encoder: Optional[EncoderConfig] = None
    n_aux_tokens: int = 0    # VLM image tokens / audio frames fed via cross-attn
    # serving
    long_context_window: int = 8192   # sliding-window variant for long_500k
    # numerics / distribution
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    fsdp: bool = False       # additionally shard params over the data axis
    seq_parallel_residual: bool = False  # Megatron-style sequence parallelism
    remat: bool = True
    optimizer: str = "adamw"  # adamw | adafactor (XL archs)
    attn_chunk: int = 1024   # flash-attention KV chunk
    # paper technique defaults for this arch
    bottleneck_ratio: int = 4   # R_c = d_model / (d_model // ratio)
    quant_bits: int = 8
    # beyond-paper: the paper's Eq.1 quantizer applied to the KV cache
    # (int8 symmetric, per-(slot, kv-head) scales). 0 = off.
    kv_quant_bits: int = 0
    # route the SSD intra-chunk computation through the Pallas kernel
    # (kernels/ssd_intra.py). Off by default: on CPU the kernel runs in
    # interpret mode (correct but slow); flip on for TPU deployments.
    use_pallas_ssd: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_head if self.d_head else self.d_model // self.n_heads

    @property
    def pattern_len(self) -> int:
        return len(self.block_pattern)

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    def block_types(self) -> Tuple[str, ...]:
        """Block type of each of the n_layers layers: the leading dense
        layers, then block_pattern repeated cyclically."""
        p, k = self.block_pattern, self.first_dense_layers
        return ("dense",) * k + tuple(
            p[i % len(p)] for i in range(self.n_layers - k))

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Input shapes assigned to this paper (public pool).
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


def reduced(cfg: ModelConfig, *, n_layers: int = 2, d_model: int = 256,
            vocab: int = 512) -> ModelConfig:
    """Reduced variant of the same family for CPU smoke tests
    (<=2 layers, d_model<=512, <=4 experts)."""
    d_model = min(d_model, 512)
    n_heads = max(2, min(cfg.n_heads, 4))
    n_kv = max(1, min(cfg.n_kv_heads, n_heads))
    kw = dict(
        n_layers=n_layers, d_model=d_model, n_heads=n_heads, n_kv_heads=n_kv,
        d_head=d_model // n_heads, d_ff=2 * d_model, vocab_size=vocab,
        param_dtype="float32", compute_dtype="float32", fsdp=False,
        attn_chunk=64, window=min(cfg.window, 64) if cfg.window else 0,
        long_context_window=128,
    )
    if cfg.moe is not None:
        # capacity_factor = n_experts => capacity == t*top_k: no token is ever
        # dropped, keeping reduced-config tests deterministic across batching.
        kw["moe"] = dataclasses.replace(
            cfg.moe, n_experts=4, top_k=2, d_expert=d_model // 2,
            n_shared_experts=min(cfg.moe.n_shared_experts, 1),
            capacity_factor=4.0)
    if cfg.mla:
        kw.update(kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
                  v_head_dim=32, q_lora_rank=48 if cfg.q_lora_rank else 0)
    if cfg.ssm is not None:
        kw["ssm"] = dataclasses.replace(
            cfg.ssm, d_state=16, head_dim=32, chunk=16)
    if cfg.encoder is not None:
        kw["encoder"] = dataclasses.replace(cfg.encoder, n_layers=2, n_frames=16)
    if cfg.n_aux_tokens:
        kw["n_aux_tokens"] = 16
    # keep the pattern but make sure n_layers covers it
    kw["first_dense_layers"] = min(cfg.first_dense_layers, 1)
    kw["n_layers"] = max(n_layers, kw["first_dense_layers"]
                         + len(cfg.block_pattern))
    return cfg.replace(**kw)
