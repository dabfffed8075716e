"""A run with the timed path broken underneath comes out not correct, and so
does the control; a sound run comes out correct. The harness's look for a
chip is skipped; everything else is a benchmark run at a tiny size, judged
by the cell's own limits."""
import dataclasses

import jax
import numpy as np
import pytest

import run
from conftest import tiny_cell
from bench.harness import oracle
from bench.harness.drive import Program
from bench.reference.fluid_ref import Reference

SYNC = ("femnist_cnn.sync128_e5", None)
POP = ("femnist_cnn.sync128_e5", "pop512_e1")   # the population entry


def _run(name, quiet_log):
    return run.run_cell(tiny_cell(*name), 2 ** 40 + 3, 0.5, False,
                        require_tpu=False, log=quiet_log)


@pytest.mark.parametrize("name", [SYNC, POP], ids=["cohort", "population"])
def test_sound_run_is_correct(name, quiet_log):
    res = _run(name, quiet_log)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"samples_per_s", "train_mfu", "setup_s"}


def _unchanged(self, params, bank, idx, xs, ys, sw, lrs, weights):
    """Every client returns the state it was given: zero deltas."""
    deltas, extra = FleetEngine_execute(self, params, bank, idx, xs, ys, sw,
                                        lrs, weights)
    return jax.tree.map(lambda d: d * 0, deltas), extra


def _half_batch(self, n_steps=None):
    """Half of every batch left out; the loss is the mean over the rest."""
    xs, ys, sw = FleetEngine_stacked(self, n_steps)
    return xs, ys, sw.at[:, :, sw.shape[2] // 2:].set(0.0)


def _altered_plan(*a, **k):
    """The calibration's answer altered where it is made: one straggler
    fewer in every plan."""
    plan = plan_from_store(*a, **k)
    return dataclasses.replace(plan, stragglers=plan.stragglers[1:])


from repro.core import straggler as _straggler  # noqa: E402
from repro.fl.fleet import FleetEngine  # noqa: E402

FleetEngine_execute = FleetEngine._execute
FleetEngine_stacked = FleetEngine._stacked_data
plan_from_store = _straggler.plan_from_store

FAULTS = {
    "state_unchanged": (FleetEngine, "_execute", _unchanged),
    "half_batch": (FleetEngine, "_stacked_data", _half_batch),
    "answer_altered": (_straggler, "plan_from_store", _altered_plan),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", [SYNC, POP], ids=["cohort", "population"])
def test_fault_is_not_correct(name, fault, monkeypatch, quiet_log):
    owner, attr, repl = FAULTS[fault]
    monkeypatch.setattr(owner, attr, repl)
    res = _run(name, quiet_log)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", [SYNC, POP], ids=["cohort", "population"])
def test_control_is_not_correct(name):
    """The reference in bfloat16, put in the program's place."""
    cell = tiny_cell(*name)
    cfg, traffic = cell["config"], cell["traffic"]
    drv = Program(cfg, cell["family"], traffic, 11)
    theta0 = jax.tree.map(np.asarray, drv.theta0)
    ref = Reference(cfg, traffic, 11)
    recs = ref.run(theta0, 3)
    ctrl = Reference(cfg, traffic, 11, variant="bf16").run(theta0, 3)
    rows = oracle.eval_rows(len(ref.y), 12)
    numbers = oracle.compare(cell["family"], cfg, theta0, ctrl, recs,
                             ref.x[rows], ref.y[rows])
    correct, _ = oracle.judge(numbers, cell["limits"])
    assert not correct, numbers
