"""The routed experts' grouped matmuls' share of their roofline over the
traced window: the least time their operations and bytes
(``costs/moe_experts.py``, from the configuration's widths and the
tokens of a batch) take at the chip's peaks, times the edge layers and
the batches, over the device time of the ops under the ``moe_experts``
scope."""
from bench import layer_scopes


def read(ctx):
    batches = ctx.info["batches"]
    ns = layer_scopes.op_ns(ctx, "moe_experts")
    if not batches or ns <= 0:
        return None
    tokens = ctx.info["batch"] * ctx.info["tokens"]
    c = ctx.cost("moe_experts").cost(ctx.cell.config, tokens)
    least_s = max(c["flops"] / ctx.peaks["bf16_flops_per_s"],
                  c["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    layers = ctx.info["edge_layers"]
    return 100.0 * batches * layers * least_s / (ns / 1e9)
