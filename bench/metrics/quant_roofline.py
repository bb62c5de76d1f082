"""Roofline share of the standalone block-wise quantize/pack and
unpack/dequantize kernels: the least time for the bytes they must move
(f32 blocks in, packed codes with zero and range out, and back) at the
HBM bandwidth, over their summed device time in the traced window."""


def read(ctx):
    least, busy = ctx.window.kernel_totals(ctx.ops, ("quant", "dequant"),
                                           ctx.peaks)
    if busy <= 0:
        return None
    return 100.0 * least / busy
