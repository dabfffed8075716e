"""Required training FLOPs of the window's rounds over window x chips x the
chip's peak, in %. FLOPs are those of the sub-model each client trained, at
its kept widths (the configuration family's `train_flops`)."""


def read(ctx):
    return 100.0 * ctx.flops / (ctx.window_s * ctx.chips * ctx.peak_flops)
