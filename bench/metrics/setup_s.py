"""Process start until the first timed round: data, weights, compilation or
compile-cache loads, and the warm-up rounds."""


def read(ctx):
    return ctx.setup_s
