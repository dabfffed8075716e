"""Mean of the program's own calibration span per round
(core/fluid RoundLog.calib_time: invariant stats, threshold, plan), in ms."""


def read(ctx):
    return 1e3 * sum(r.calib_s for r in ctx.rounds) / len(ctx.rounds)
