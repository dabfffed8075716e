"""The reduction from a recorded TPU trace to the per-layer metrics.

`data/tiny_cohort.xplane.pb` is the profiler trace of a traced benchmark
run of a tiny fixed cohort (8 FEMNIST-CNN clients of 40 samples, 1 epoch,
two rounds in the window), recorded by the harness on one TPU v5e."""
import importlib

import pytest

from conftest import ROOT
from bench.harness.context import RoundStat, RunContext
from bench.harness.trace import ROUND_MARK, breakdown, events_of, summarize

XPLANE = ROOT / "bench" / "tests" / "data" / "tiny_cohort.xplane.pb"
PER_LAYER = ("device_idle_share", "window_compiles", "cohort_sgd_ms",
             "cohort_sgd_roofline", "aggregate_ms", "calib_ms", "round_mfu")


@pytest.fixture(scope="module")
def events():
    return events_of(str(XPLANE))


def test_summary(events):
    s = summarize(events)
    marks = [(a, b) for _, _, n, a, b in events if n == ROUND_MARK]
    assert len(marks) == 2
    assert s.window_s == pytest.approx(
        (max(b for _, b in marks) - min(a for a, _ in marks)) * 1e-9)
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    assert {"jit_run", "jit_aggregate_stacked"} <= set(s.module_s)
    assert s.module_s["jit_run"] <= s.busy_s
    assert s.collective_s == 0.0 and s.collective_exposed_s == 0.0


def test_readers(events):
    """Each per-layer reader turns the trace and the counts into a value in
    its range; the device-time readers agree with the module times."""
    s = summarize(events)
    flops = 8 * 40 * 28.6e6
    ctx = RunContext(cell="tiny", chips=1, peak_flops=197e12,
                     peak_bytes_per_s=819e9, setup_s=1.0, window_s=s.window_s,
                     trace=s, compiles_in_window=0,
                     rounds=[RoundStat(0.2, 320, flops, 1e7, 0.01)] * 2)
    got = {n: importlib.import_module(f"bench.metrics.{n}").read(ctx)
           for n in PER_LAYER}
    assert got["cohort_sgd_ms"] == pytest.approx(
        1e3 * s.module_s["jit_run"] / 2)
    assert got["aggregate_ms"] == pytest.approx(
        1e3 * s.module_s["jit_aggregate_stacked"] / 2)
    assert got["device_idle_share"] == pytest.approx(
        100 * (1 - s.busy_s / s.window_s))
    assert 0 < got["cohort_sgd_roofline"] <= 100
    assert got["calib_ms"] == pytest.approx(10.0)
    assert got["window_compiles"] == 0.0
    assert 0 < got["round_mfu"] < 100


def test_no_trace_reads_nothing():
    ctx = RunContext(cell="t", chips=1, peak_flops=1.0, peak_bytes_per_s=1.0,
                     setup_s=1.0, window_s=1.0,
                     rounds=[RoundStat(1.0, 1, 1.0, 1.0, 0.0)])
    for n in ("device_idle_share", "cohort_sgd_ms", "cohort_sgd_roofline",
              "aggregate_ms", "round_mfu"):
        assert importlib.import_module(f"bench.metrics.{n}").read(ctx) is None


def test_breakdown_shape(events):
    b = breakdown(summarize(events))
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(isinstance(k, str) and v > 0
               for k, v in b["device_ops"] + b["idle_gaps"])
