"""The plain reference against the program's own sequential path, and its
control: at a tiny size on the CPU."""
import jax
import numpy as np

from conftest import tiny_cell
from bench.harness import oracle
from bench.harness.drive import Program
from bench.reference.fluid_ref import Reference

CELL = "femnist_cnn.sync128_e5"


def _program(cell, backend, rounds=2, seed=7):
    cell["traffic"]["backend"] = backend
    drv = Program(cell["config"], cell["family"], cell["traffic"], seed)
    snaps = [drv.setup_round() for _ in range(rounds)]
    return jax.tree.map(np.asarray, drv.theta0), snaps


def test_matches_sequential_backend():
    """Two rounds (full models, then a straggler on an extracted
    sub-model): the reference reproduces SequentialBackend + aggregate to
    float32 rounding, with the same cohort, plans and keep maps."""
    cell = tiny_cell(CELL)
    theta0, snaps = _program(cell, "sequential")
    recs = Reference(cell["config"], cell["traffic"], 7).run(theta0, 2)
    assert snaps[1].sub_model_clients == recs[1].sub_model_clients == [0]
    for s, r in zip(snaps, recs):
        assert s.cohort == r.cohort and s.stragglers == r.stragglers
        for g in r.keep_next:
            np.testing.assert_array_equal(s.keep_next[g], r.keep_next[g])
        for a, b in zip(jax.tree.leaves(s.params), jax.tree.leaves(r.params)):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)


def test_tells_bfloat16_from_float32():
    """Local SGD in bfloat16 moves the compared numbers far more than the
    program's float32 path does at the same size."""
    cell = tiny_cell(CELL)
    theta0, snaps = _program(cell, "fleet", rounds=3)
    cfg, traffic = cell["config"], cell["traffic"]
    ref = Reference(cfg, traffic, 7)
    recs = ref.run(theta0, 3)
    bf16 = Reference(cfg, traffic, 7, variant="bf16").run(theta0, 3)
    rows = oracle.eval_rows(len(ref.y), 8)
    x, y = ref.x[rows], ref.y[rows]
    prog = oracle.compare(cell["family"], cfg, theta0, snaps, recs, x, y)
    ctrl = oracle.compare(cell["family"], cfg, theta0, bf16, recs, x, y)
    assert ctrl["change_gap"] > 100 * max(prog["change_gap"], 1e-6)
    assert ctrl["update_gap"] > 100 * max(prog["update_gap"], 1e-6)
