"""The host's wait per round for the non-straggler statistics, in ms:
the program's `fleet.stats_wait` span (fl/fleet
CohortResult.non_straggler_stats: the gather and stats dispatch, then the
wait on them and on the cohort program before them)."""
from bench.harness.program_spans import span_ms


def read(ctx):
    return span_ms(ctx, "fleet.stats_wait")
