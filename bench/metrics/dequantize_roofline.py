"""The dequantize kernel's share of its roofline over the traced window:
the least time its operations and bytes (``costs/dequantize.py``, from the
code shape) take at the chip's peaks, times the calls, over the device
time of the kernel's ops in the trace: those named after it, or the
custom call (the Pallas kernel) that takes the uint8 codes."""
import re

KERNEL = re.compile(r"dequant|custom-call\(u8\[", re.IGNORECASE)


def read(ctx):
    batches = ctx.info["batches"]
    kernel_ns = sum(ctx.trace.op_ns(*ctx.window, match=KERNEL).values())
    if not batches or kernel_ns <= 0:
        return None
    elems = 1
    for d in ctx.info["code_shape"]:
        elems *= d
    c = ctx.cost("dequantize").cost(elems, bits=ctx.info["bits"])
    least_s = max(c["flops"] / ctx.peaks["bf16_flops_per_s"],
                  c["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * batches * least_s / (kernel_ns / 1e9)
