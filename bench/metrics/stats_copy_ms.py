"""Per-client device-to-host copies of the statistics per round, in ms:
the program's `fleet.stats_copy` span (fl/fleet
CohortResult.non_straggler_stats)."""
from bench.harness.program_spans import span_ms


def read(ctx):
    return span_ms(ctx, "fleet.stats_copy")
