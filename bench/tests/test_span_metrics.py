"""The readers of the program's own spans and counters (repro.core.spans):
the mean per round of the window, and None when nothing was recorded."""
import importlib
import sys

import pytest

import repro.core
from bench.harness.context import RoundStat, RunContext
from repro.core import spans

SPANS = {"stage_ms": "fleet.stage", "stats_wait_ms": "fleet.stats_wait",
         "stats_copy_ms": "fleet.stats_copy", "threshold_ms": "fluid.threshold",
         "plan_ms": "fluid.plan"}
COUNTERS = {"threshold_iters": "fluid.threshold_iters",
            "stats_copies": "fleet.stats_copies"}


def _ctx(n_rounds):
    return RunContext(cell="t", chips=1, peak_flops=1.0, peak_bytes_per_s=1.0,
                      setup_s=1.0, window_s=1.0,
                      rounds=[RoundStat(1.0, 1, 1.0, 1.0, 0.0)] * n_rounds)


def _record_rounds(per_round):
    """Close one record per entry: {name: value} into spans or counters."""
    for values in per_round:
        with spans.record() as rec:
            for name, v in values.items():
                (rec.counters if isinstance(v, int) else rec.spans)[name] = v


def _read(metric, ctx):
    return importlib.import_module(f"bench.metrics.{metric}").read(ctx)


@pytest.mark.parametrize("metric", sorted(SPANS) + sorted(COUNTERS))
def test_mean_per_round_of_the_window(metric):
    name = SPANS.get(metric) or COUNTERS[metric]
    if metric in SPANS:
        rounds, want = [{name: 9.0}, {name: 0.25}, {name: 0.75}], 500.0
    else:
        rounds, want = [{name: 1000}, {name: 3}, {name: 5}], 4.0
    _record_rounds(rounds)     # the first is a set-up round, not the window
    assert _read(metric, _ctx(2)) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(SPANS) + sorted(COUNTERS))
def test_none_when_nothing_recorded(metric, monkeypatch):
    """Nothing of the name in the window's records, no window, or a
    program without the records at all (as before they existed)."""
    name = SPANS.get(metric) or COUNTERS[metric]
    one = 1.0 if metric in SPANS else 1
    _record_rounds([{name: one}, {"other": 1.0}, {"other": 2}])
    assert _read(metric, _ctx(2)) is None
    assert _read(metric, _ctx(0)) is None
    _record_rounds([{name: one}, {name: one}])
    assert _read(metric, _ctx(2)) is not None
    monkeypatch.delattr(repro.core, "spans")
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert _read(metric, _ctx(2)) is None
