"""The program's own host spans and counters, per round of the window, for
the readers in bench/metrics/ (the program's side is repro.core.spans).

The program keeps the records of its last rounds, newest last. Nothing runs
a round between the window and the readers, so the window's rounds are the
last `len(ctx.rounds)` records. Against a program without those records, or
a window in which no round recorded the name, a reader returns None.
"""
from __future__ import annotations


def _window(ctx):
    try:
        from repro.core import spans
    except ImportError:
        return None
    n = len(ctx.rounds)
    recs = spans.last_rounds(n)
    return recs if n and len(recs) == n else None


def _mean(ctx, kind: str, name: str):
    recs = _window(ctx)
    if recs is None:
        return None
    vals = [getattr(r, kind).get(name) for r in recs]
    if all(v is None for v in vals):
        return None
    return sum(v or 0 for v in vals) / len(vals)


def span_ms(ctx, name: str):
    """Mean host time of span `name` per round of the window, in ms."""
    m = _mean(ctx, "spans", name)
    return None if m is None else 1e3 * m


def counter(ctx, name: str):
    """Mean of counter `name` per round of the window."""
    return _mean(ctx, "counters", name)
