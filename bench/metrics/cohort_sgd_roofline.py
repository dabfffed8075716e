"""Least time the chip could take for the window's local SGD over the
cohort program's device time, in %. The least time is the larger of the
required FLOPs over the peak and the least bytes over the HBM bandwidth,
both counted from the algorithm's work (real samples, kept widths); the
harness prints which of the two bounds it."""

from bench.metrics import cohort_sgd_ms


def bound(ctx):
    """(seconds, "flops" | "bytes") of the least time of the window."""
    t_f = ctx.flops / (ctx.chips * ctx.peak_flops)
    t_b = sum(r.min_bytes for r in ctx.rounds) / (ctx.chips
                                                  * ctx.peak_bytes_per_s)
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")


def read(ctx):
    ms = cohort_sgd_ms.read(ctx)
    if not ms:
        return None
    return 100.0 * ctx.ms_per_round(bound(ctx)[0]) / ms
