"""Passes of the threshold search per round: the program's
`fluid.threshold_iters` counter (one per count_invariant pass inside
core/invariant.calibrate_threshold)."""
from bench.harness.program_spans import counter


def read(ctx):
    return counter(ctx, "fluid.threshold_iters")
