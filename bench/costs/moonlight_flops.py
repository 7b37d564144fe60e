"""Model FLOPs of the DeepSeek-V3-style edge half from the configuration's
published widths: two per multiply-add of every matrix product a token
needs, nothing for norms, RoPE, activations, the softmax or additions.
Causal attention at T tokens attends (T + 1) / 2 keys a query on
average. Read from the configuration, not from the program."""
from __future__ import annotations


def moe_layer_flops(c, tokens):
    """FLOPs per token of one MoE layer at a prompt of ``tokens`` tokens,
    by part."""
    d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    dn, dr = int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"])
    dv, r = int(c["v_head_dim"]), int(c["kv_lora_rank"])
    f, e = int(c["moe_intermediate_size"]), int(c["n_routed_experts"])
    q = d * h * (dn + dr)
    if c.get("q_lora_rank"):
        q = d * int(c["q_lora_rank"]) + int(c["q_lora_rank"]) * h * (dn + dr)
    return {
        "routed": 2 * 3 * d * f * int(c["num_experts_per_tok"]),
        "shared": 2 * 3 * d * f * int(c["n_shared_experts"]),
        "mla_proj": 2 * (q + d * (r + dr) + r * h * (dn + dv) + h * dv * d),
        "attention": 2 * h * (dn + dr + dv) * (tokens + 1) / 2,
        "router": 2 * d * e,
    }


def edge_step_flops(c, batch, tokens):
    """FLOPs of one batch's edge step: the codec's decode and every MoE
    layer for each token, the head at each request's last position."""
    d = int(c["hidden_size"])
    layers = int(c["num_hidden_layers"]) - int(c["first_k_dense_replace"])
    decode = 2 * (d // int(c["ae_ratio"][0])) * d
    per_token = decode + layers * sum(moe_layer_flops(c, tokens).values())
    head = 2 * d * int(c["vocab_size"])
    return batch * (tokens * per_token + head)
