"""Straggler planning per round, in ms: the program's `fluid.plan` span
(core/fluid: plan_from_store, the drop target and the store's rate
write-back)."""
from bench.harness.program_spans import span_ms


def read(ctx):
    return span_ms(ctx, "fluid.plan")
