"""The whole epoch step's share of the chip's peak: model FLOPs per
epoch (``counts.model_flops``: forward matmuls plus weight and input
gradients, none for layer 0's input) times the epochs completed in the
traced window, over the window's wall time and the chips' bf16 peak."""


def read(ctx):
    if ctx.epochs == 0:
        return None
    chips = ctx.cell["chips"]
    return 100.0 * ctx.model_flops * ctx.epochs / (
        ctx.wall_s * chips * ctx.peaks["flops"])
