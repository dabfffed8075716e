"""The whole round's share of the chip's peak in the traced run, in %:
required training FLOPs of its rounds over window x chips x peak. It bounds
what a kernel's roofline share can add end to end."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.flops / (ctx.window_s * ctx.chips * ctx.peak_flops)
