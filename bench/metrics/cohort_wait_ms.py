"""The host's wait per round on the cohort's local-SGD program, in ms: the
program's `fleet.cohort_wait` span (fl/fleet
CohortResult.non_straggler_stats: a block on the cohort's deltas, inside
`fleet.stats_wait` and before the gather and the stats program are
dispatched). None against a program without that span."""
from bench.harness.program_spans import span_ms


def read(ctx):
    return span_ms(ctx, "fleet.cohort_wait")
