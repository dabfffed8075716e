"""Host spans and counters of a FLuID round (core/spans.py).

A tiny FEMNIST-CNN fleet cohort with planted stragglers: the round log
carries the calibration's spans and counters, they agree with the work the
round did, recording changes no number the round computes, and a profiler
trace shows every span on a host plane.
"""
import contextlib

import jax
import numpy as np
import pytest

from repro.core import invariant as inv
from repro.core import spans
from repro.fl.simulation import (CohortConfig, SimulationConfig,
                                 build_simulation)

CALIB_CHILDREN = ("fleet.stats_wait", "fleet.stats_copy", "fluid.threshold",
                  "fluid.plan")
ROUND_SPANS = ("fleet.stage", "fluid.calibrate") + CALIB_CHILDREN


def _sim():
    return build_simulation(SimulationConfig(
        workload="femnist", backend="fleet", policy="invariant", seed=0,
        cohort=CohortConfig(n_clients=6, straggler_ids=(0, 1), n_data=240)))


def _snapshot(sim):
    srv = sim.server
    return {"params": jax.tree.map(np.asarray, srv.params),
            "stragglers": list(srv.plan.stragglers),
            "rates": dict(srv.plan.rates),
            "store_rates": np.asarray(srv.store.rates_of(
                [c.id for c in sim.clients])),
            "th": srv.th}


@pytest.fixture(scope="module")
def rounds():
    """Two rounds (full model, then dropout), with the arguments of every
    threshold search and the sub-model clients of the dropout round."""
    sim = _sim()
    searches = []
    orig = inv.calibrate_threshold

    def spy(stats, target, th0, *a, **k):
        searches.append((stats, target, th0))
        return orig(stats, target, th0, *a, **k)

    ids = [c.id for c in sim.clients]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(inv, "calibrate_threshold", spy)
        log0 = sim.server.run_round()
        n0 = len(searches)
        n_sub = int((np.asarray(sim.server.store.rates_of(ids)) < 1.0).sum())
        log1 = sim.server.run_round()
    return {"sim": sim, "logs": (log0, log1), "n_sub": n_sub,
            "searches": searches[n0:], "snap": _snapshot(sim)}


def test_recorder_collects_nested_spans_and_counters():
    with spans.record() as rec:
        with spans.span("outer"):
            with spans.span("inner"):
                spans.count("c")
            spans.count("c", 3)
        with spans.span("inner"):
            pass
    assert set(rec.spans) == {"outer", "inner"}
    assert rec.spans["outer"] > 0.0 and rec.spans["inner"] > 0.0
    assert rec.counters == {"c": 4}
    assert spans.last_rounds(1) == [rec]


def test_nothing_recorded_without_a_recorder():
    with spans.span("loose"):
        spans.count("loose")
    spans.count("loose", 5)
    assert spans.last_rounds(0) == []
    with spans.record() as rec:
        pass
    assert rec.spans == {} and rec.counters == {}


def test_round_log_holds_the_spans(rounds):
    sim, (_, log) = rounds["sim"], rounds["logs"]
    assert rounds["n_sub"] > 0, "the second round must train sub-models"
    assert set(ROUND_SPANS) <= set(log.spans)
    assert log.calib_time == log.spans["fluid.calibrate"]
    assert sum(log.spans[k] for k in CALIB_CHILDREN) <= log.calib_time
    assert spans.last_rounds(sim.server.round)[-1].spans == log.spans


def test_cohort_wait_nested_in_stats_wait(rounds, tmp_path):
    """The wait on the cohort program has its own span inside
    `fleet.stats_wait`: in the round log it is no longer than its parent,
    and on the profiler's host plane it lies within the parent's extent."""
    for log in rounds["logs"]:
        assert 0.0 < log.spans["fleet.cohort_wait"] \
            <= log.spans["fleet.stats_wait"]
    with jax.profiler.trace(str(tmp_path)):
        rounds["sim"].server.run_round()
    path = next(tmp_path.rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    ext = {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("fleet.stats_wait", "fleet.cohort_wait"):
                    ext.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    (p0, p1), = ext["fleet.stats_wait"]
    (c0, c1), = ext["fleet.cohort_wait"]
    assert p0 <= c0 <= c1 <= p1


def test_stats_copies_counter(rounds):
    """One device-to-host copy per unit group, whatever the number of
    non-straggler clients."""
    log = rounds["logs"][1]
    n_non = len(rounds["sim"].clients) - rounds["n_sub"]
    groups = len(rounds["sim"].model_cls.UNIT_SPECS)
    assert n_non > 1
    assert log.counters["fleet.stats_copies"] == groups


def test_client_data_uploads_once(rounds):
    """An engine uploads its clients' samples in its first round and never
    again; every round still stages its sample orders."""
    sim, (log0, log1) = rounds["sim"], rounds["logs"]
    log2 = sim.server.run_round()
    assert log0.counters["fleet.data_uploads"] == 1
    assert "fleet.data_upload" in log0.spans
    for log in (log1, log2):
        assert log.counters.get("fleet.data_uploads", 0) == 0
        assert "fleet.data_upload" not in log.spans
    for log in (log0, log1, log2):
        assert "fleet.stage" in log.spans


def test_threshold_iters_counter(rounds):
    """An independent recount of calibrate_threshold's passes on the stats,
    target and start threshold the round searched with."""
    log = rounds["logs"][1]
    assert rounds["searches"], "the dropout round must search a threshold"
    passes = 0
    for stats, target, th0 in rounds["searches"]:
        th = max(float(th0), inv.EPS)
        for _ in range(200):
            passes += 1
            if inv.count_invariant(stats, th) >= target:
                break
            th *= inv.TH_GROWTH
    assert log.counters["fluid.threshold_iters"] == passes


def test_recording_changes_nothing(rounds, monkeypatch):
    """Params, plans and store rates after two rounds are bitwise equal
    with and without an open recorder."""
    @contextlib.contextmanager
    def inert():
        yield spans.Record()
    monkeypatch.setattr(spans, "record", inert)
    sim = _sim()
    for _ in range(2):
        log = sim.server.run_round()
    assert log.spans == {} and log.counters == {}
    got, want = _snapshot(sim), rounds["snap"]
    assert got["stragglers"] == want["stragglers"]
    assert got["rates"] == want["rates"]
    assert got["th"] == want["th"]
    np.testing.assert_array_equal(got["store_rates"], want["store_rates"])
    for a, b in zip(jax.tree.leaves(got["params"]),
                    jax.tree.leaves(want["params"])):
        np.testing.assert_array_equal(a, b)


def test_profiler_trace_holds_every_span(rounds, tmp_path):
    sim = rounds["sim"]
    with jax.profiler.trace(str(tmp_path)):
        sim.server.run_round()
    path = next(tmp_path.rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    host = {ev.name for plane in data.planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events}
    assert set(ROUND_SPANS) <= host
