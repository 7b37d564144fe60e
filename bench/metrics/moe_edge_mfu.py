"""Model FLOP/s utilization of the transformer edge half over the traced
window: the model FLOPs of a batch's edge step
(``costs/moonlight_flops.py``: the codec's decode, every edge layer and
the head at each request's last position), times the batches completed,
over the window and the chip's bf16 peak."""


def read(ctx):
    batches = ctx.info["batches"]
    if not batches or not ctx.trace.ops or ctx.window_s <= 0:
        return None
    flops = ctx.cost("moonlight_flops").edge_step_flops(
        ctx.cell.config, ctx.info["batch"], ctx.info["tokens"])
    return 100.0 * flops * batches / ctx.window_s \
        / ctx.peaks["bf16_flops_per_s"]
