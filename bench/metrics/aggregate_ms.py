"""Device time per round of the masked FedAvg program
(core/aggregate.aggregate_stacked, `jit_aggregate_stacked`), in ms."""

MODULE = "jit_aggregate_stacked"


def read(ctx):
    if ctx.trace is None or MODULE not in ctx.trace.module_s:
        return None
    return ctx.ms_per_round(ctx.trace.module_s[MODULE])
