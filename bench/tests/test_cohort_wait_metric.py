"""The reader of the program's `fleet.cohort_wait` span: the mean per round
of the window, and None against a program that records no such span (the
program before the span existed)."""
import sys

import pytest

import repro.core
from test_span_metrics import _ctx, _read, _record_rounds

NAME = "fleet.cohort_wait"


def test_mean_per_round_of_the_window():
    # the first record is a set-up round, not the window
    _record_rounds([{NAME: 9.0}, {NAME: 0.25}, {NAME: 0.75}])
    assert _read("cohort_wait_ms", _ctx(2)) == pytest.approx(500.0)


def test_none_without_the_span(monkeypatch):
    _record_rounds([{NAME: 1.0}, {"fleet.stats_wait": 1.0},
                    {"fleet.stats_wait": 2.0}])
    assert _read("cohort_wait_ms", _ctx(2)) is None
    assert _read("cohort_wait_ms", _ctx(0)) is None
    monkeypatch.delattr(repro.core, "spans")
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert _read("cohort_wait_ms", _ctx(2)) is None
