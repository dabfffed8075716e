"""The VGG-9 cell at a tiny size on the CPU: a sound run comes out correct
by the cell's own limits, and the two deviations its limits were set
against (local SGD in bfloat16, half of every batch left out) do not."""
import jax
import numpy as np
import pytest

import run
from conftest import tiny_cell
from bench.harness import oracle
from bench.harness.drive import Program
from bench.reference.fluid_ref import Reference

CELL = "cifar_vgg9.sync64_e1"
SEED = 2 ** 40 + 3


def test_sound_run_is_correct(quiet_log):
    res = run.run_cell(tiny_cell(CELL), SEED, 0.5, False, require_tpu=False,
                       log=quiet_log)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"samples_per_s", "train_mfu", "setup_s"}


@pytest.fixture(scope="module")
def program_and_reference():
    cell = tiny_cell(CELL)
    cfg, traffic = cell["config"], cell["traffic"]
    drv = Program(cfg, cell["family"], traffic, 11)
    snaps = [drv.setup_round() for _ in range(3)]
    theta0 = jax.tree.map(np.asarray, drv.theta0)
    ref = Reference(cfg, traffic, 11)
    rows = oracle.eval_rows(len(ref.y), 12)
    return cell, theta0, snaps, ref.run(theta0, 3), ref.x[rows], ref.y[rows]


@pytest.mark.parametrize("variant", ["bf16", "half_batch"])
def test_deviation_is_not_correct(program_and_reference, variant):
    """The reference with the deviation planted, put in the program's
    place, fails at least one of the cell's limits; the program itself,
    on the same seed, passes them all."""
    cell, theta0, snaps, recs, x, y = program_and_reference
    cfg, traffic, fam = cell["config"], cell["traffic"], cell["family"]
    alt = Reference(cfg, traffic, 11, variant=variant).run(theta0, 3)
    numbers = oracle.compare(fam, cfg, theta0, alt, recs, x, y)
    assert not oracle.judge(numbers, cell["limits"])[0], numbers
    sound = oracle.compare(fam, cfg, theta0, snaps, recs, x, y)
    assert oracle.judge(sound, cell["limits"])[0], sound
