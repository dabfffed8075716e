"""Host staging per round, in ms: the program's `fleet.stage` span
(fl/fleet FleetEngine._stacked_data: the cohort's batches built on the host,
then copied to the device)."""
from bench.harness.program_spans import span_ms


def read(ctx):
    return span_ms(ctx, "fleet.stage")
