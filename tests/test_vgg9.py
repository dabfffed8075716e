"""The paper's CIFAR-10 VGG-9 (models/small.Vgg9) against the benchmark's
plain reference (bench/reference/seq_cnn.py on bench/configs/cifar_vgg9.json,
and bench/reference/fluid_ref.py for whole rounds), on seeded random
weights at a tiny size on the CPU.

Tolerances: on the CPU both sides compute in float32, so they differ only
by the order of float32 sums. The round comparison read ~1e-7 at this
size; each limit below leaves three orders of magnitude above that and
sits far below what local SGD in bfloat16 reads (~0.3 on the same cohort).
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "bench", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench.harness import oracle  # noqa: E402
from bench.harness.drive import Program  # noqa: E402
from bench.reference import seq_cnn  # noqa: E402
from bench.reference.fluid_ref import Reference  # noqa: E402
from repro.core import submodel as sub  # noqa: E402
from repro.models.small import Vgg9  # noqa: E402

CFG = json.loads((ROOT / "bench/configs/cifar_vgg9.json").read_text())
TRAFFIC = dict(json.loads((ROOT / "bench/traffic/sync64_e1.json").read_text()),
               n_clients=4, samples_per_client=40,
               straggler_ids={"first": 1})
RATE = TRAFFIC["fixed_rate"]


def _params(seed, bias=True):
    """seq_cnn.init's weights, with random biases so that they count too."""
    p = seq_cnn.init(CFG, jax.random.PRNGKey(seed))
    if bias:
        keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), len(p)))
        p = {n: {"w": l["w"],
                 "b": 0.1 * jax.random.normal(next(keys), l["b"].shape)}
             for n, l in p.items()}
    return p


def _keep(seed):
    """A random keep map at the cell's rate over every droppable layer."""
    rng = np.random.RandomState(seed)
    return {name: np.sort(rng.choice(size, seq_cnn.keep_count(size, RATE),
                                     replace=False))
            for name, size in seq_cnn.droppable(CFG)}


def test_config_matches_model():
    """The configuration describes the program's model: the same leaves,
    shapes and parameter count, and one droppable layer per unit group."""
    prog = jax.eval_shape(Vgg9.init, jax.random.PRNGKey(0))
    ref = jax.eval_shape(lambda k: seq_cnn.init(CFG, k), jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, prog) == \
        jax.tree.map(lambda a: a.shape, ref)
    assert seq_cnn.param_count(CFG) == CFG["params"] == sum(
        a.size for a in jax.tree.leaves(prog))
    assert [g["name"] for g in Vgg9.UNIT_SPECS] == \
        [n for n, _ in seq_cnn.droppable(CFG)]


def test_forward_matches_reference():
    """Logits of Vgg9.apply and the reference's forward at "highest" on the
    same weights agree to float32 rounding (1e-5 of the largest logit)."""
    params = _params(3)
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 32, 32, 3))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(Vgg9.apply(params, x))
    want = np.asarray(seq_cnn.forward(CFG, params, x))
    assert got.shape == (8, 10)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * np.abs(want).max())


def test_flatten_keep_map_matches_extract():
    """The conv-to-FC keep map (16 spatial positions, channel fastest):
    core/submodel.extract gathers exactly the reference's sub-model, the
    dense keep mask is 1 exactly on its coordinates, and the masked full
    model computes the extracted sub-model's logits."""
    params, keep = _params(5), _keep(6)
    got = sub.extract(params, Vgg9.UNIT_SPECS, keep)
    want = seq_cnn.extract(CFG, params, keep)
    assert got["fc1"]["w"].shape == (16 * len(keep["c3b"]),
                                     len(keep["fc1"]))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _, ref_mask = seq_cnn.embed(CFG, params, want, keep)
    mask = sub.keep_mask(params, Vgg9.UNIT_SPECS, keep)
    for a, b in zip(jax.tree.leaves(mask), jax.tree.leaves(ref_mask)):
        np.testing.assert_array_equal(np.asarray(a), b)
    x = jax.random.normal(jax.random.PRNGKey(7), (4, 32, 32, 3))
    with jax.default_matmul_precision("highest"):
        masked = np.asarray(Vgg9.apply(sub.apply_mask(params, mask), x))
    small = np.asarray(seq_cnn.forward(CFG, want, x))
    np.testing.assert_allclose(masked, small, rtol=0,
                               atol=1e-5 * np.abs(small).max())


@pytest.fixture(scope="module")
def rounds():
    """Three set-up rounds of a 4-client cohort through the benchmark's
    driver (the fleet backend, as the cell runs it) and the reference."""
    drv = Program(CFG, seq_cnn, TRAFFIC, 17)
    snaps = [drv.setup_round() for _ in range(3)]
    theta0 = jax.tree.map(np.asarray, drv.theta0)
    ref = Reference(CFG, TRAFFIC, 17)
    recs = ref.run(theta0, 3)
    rows = oracle.eval_rows(len(ref.y), 18)
    return snaps, recs, oracle.compare(seq_cnn, CFG, theta0, snaps, recs,
                                       ref.x[rows], ref.y[rows])


def test_rounds_train_a_sub_model(rounds):
    """The planted straggler is flagged by round 0's calibration and trains
    the r=0.75 invariant sub-model in rounds 1 and 2."""
    snaps, recs, _ = rounds
    assert snaps[0].stragglers == [0]
    assert snaps[1].sub_model_clients == recs[1].sub_model_clients == [0]
    for name, size in seq_cnn.droppable(CFG):
        assert len(snaps[1].keep_next[name]) == seq_cnn.keep_count(size, RATE)


@pytest.mark.parametrize("number,limit", [
    ("loss_gap", 1e-4), ("update_gap", 1e-4), ("change_gap", 1e-4),
    ("plan_mismatch", 0.0)])
def test_rounds_match_reference(rounds, number, limit):
    """oracle.compare on params and plan: float32 summation order only, and
    the plans exactly."""
    numbers = rounds[2]
    assert numbers[number] <= limit, numbers
