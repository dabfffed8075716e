"""Programs compiled or loaded from the compile cache inside the window
(JAX's backend-compile events, counted by a jax.monitoring listener)."""


def read(ctx):
    return float(ctx.compiles_in_window)
