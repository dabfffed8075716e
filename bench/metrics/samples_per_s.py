"""Real local-SGD sample passes of every round of the window, per second of
the window (host clock; each round ends when the new global params are
ready)."""


def read(ctx):
    return ctx.samples / ctx.window_s
