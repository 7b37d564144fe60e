"""Model FLOP/s utilization of the edge half over the traced window: the
published layer FLOPs of the modules after the split, per image, times the
images completed, over the window and the chip's bf16 peak."""


def read(ctx):
    batches = ctx.info["batches"]
    if not batches or not ctx.trace.ops or ctx.window_s <= 0:
        return None
    flops = ctx.cost("cnn_flops").flops_per_image(
        ctx.cell.config, start=ctx.info["split_module"] + 1)
    rate = flops * batches * ctx.info["batch"] / ctx.window_s
    return 100.0 * rate / ctx.peaks["bf16_flops_per_s"]
