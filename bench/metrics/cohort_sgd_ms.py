"""Device time per round of the cohort's local-SGD program (fl/fleet's
vmapped cohort program, `jit_run` in the trace), in ms."""

MODULE = "jit_run"


def read(ctx):
    if ctx.trace is None or MODULE not in ctx.trace.module_s:
        return None
    return ctx.ms_per_round(ctx.trace.module_s[MODULE])
