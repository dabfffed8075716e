"""Threshold search per round, in ms: the program's `fluid.threshold`
span (core/fluid: initial_threshold, calibrate_threshold, policy.observe and
the invariant count of the log)."""
from bench.harness.program_spans import span_ms


def read(ctx):
    return span_ms(ctx, "fluid.threshold")
