"""Operations and bytes of one MoE layer's routed experts (the grouped
matmuls of ``moe_experts``) over ``tokens`` tokens, from the
configuration's widths: two per multiply-add of the gate, up and down
projections for each of the ``tokens * num_experts_per_tok`` routed rows;
every expert's weights read once in bfloat16, each routed row read once
in bfloat16 and its output written once in float32."""
from __future__ import annotations


def cost(config, tokens):
    d = int(config["hidden_size"])
    f = int(config["moe_intermediate_size"])
    e = int(config["n_routed_experts"])
    rows = tokens * int(config["num_experts_per_tok"])
    return {"flops": 2 * 3 * d * f * rows,
            "bytes": e * 3 * d * f * 2 + rows * d * (2 + 4)}
