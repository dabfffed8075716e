"""Per-client statistic copies per round: the program's
`fleet.stats_copies` counter (non-stragglers x unit groups, added once per
fl/fleet CohortResult.non_straggler_stats call)."""
from bench.harness.program_spans import counter


def read(ctx):
    return counter(ctx, "fleet.stats_copies")
