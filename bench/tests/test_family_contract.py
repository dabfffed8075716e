"""The model family is a plug-in: everything the benchmark does that depends
on the model goes through the module the configuration's `family` names
(the contract is in bench/reference/seq_cnn.py's docstring). On the CPU at
the tiny cut."""
import collections
import hashlib
import importlib.util
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT, tiny_cell
from bench.harness import oracle
from bench.harness.data import cohort_data
from bench.harness.drive import Program
from bench.reference import fluid_ref, seq_cnn
from bench.reference.fluid_ref import Reference

CONTRACT = ("init", "forward", "example_loss", "rows", "droppable",
            "keep_count", "extract", "embed", "unit_stats", "param_count",
            "train_flops", "sample_bytes")
SEED = 2 ** 40 + 3

# SHA-256 of cohort_data's x, y and speeds at the tiny cut and SEED, as the
# harness drew them before the rows moved into the family (the draws, their
# order and their generator are unchanged).
PINNED = {
    "femnist_cnn.sync128_e5": (
        "6105b51f5ee0ca1637cc5cb058f44987376068d9b2865a6d953ebfc2ad55a104",
        "8049f69ba215082df0ae5c54b0d56d9366f0df8896d419882b84ae8af0111662",
        "0233f90178788377ee5f5fa40c2899b65c3b3bd77b319d40b9a9c9850154d263"),
    "cifar_vgg9.sync64_e1": (
        "af87ba38a6c7bcdc0e2b6ea516280afa9352f065df41f695526599091d16f551",
        "d6f2a08ecb131bfb0fac7bd005cdd30128561d27270af573675ac8939c6622bb",
        "441728462df0844e0a5684d230b68efc8c28da3965fc7a2b7bb79d9731802912"),
}


def _sha(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


@pytest.mark.parametrize("name", sorted(PINNED))
def test_cohort_data_unchanged(name):
    """The family's rows and the speeds drawn after them are the same
    bytes as before, so every cell trains on the same data."""
    cell = tiny_cell(name)
    x, y, parts, speeds = cohort_data(cell["config"], cell["traffic"], SEED)
    assert (x.dtype, y.dtype, speeds.dtype) == (np.float32, np.int32,
                                                np.float64)
    assert (_sha(x), _sha(y), _sha(speeds)) == PINNED[name]
    assert len(parts) == cell["traffic"]["n_clients"]


# ---------------------------------------------------------------------------
# a family that is not seq_cnn

def _counted_family(name: str):
    """A copy of seq_cnn's code loaded as bench.reference.<name>, each
    contract function counting its calls (internal ones included, since
    the copy's own calls go through its counted globals)."""
    spec = importlib.util.spec_from_file_location(
        f"bench.reference.{name}", ROOT / "bench" / "reference" / "seq_cnn.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    calls = collections.Counter()

    def counted(fn_name, fn):
        def wrapper(*a, **k):
            calls[fn_name] += 1
            return fn(*a, **k)
        return wrapper
    for fn_name in CONTRACT:
        setattr(mod, fn_name, counted(fn_name, getattr(mod, fn_name)))
    return mod, calls


def _two_rounds(cell):
    """Two rounds (full models, then a straggler on its sub-model) through
    the program, the reference and the comparison, with the work counts."""
    cfg, traffic, fam = cell["config"], cell["traffic"], cell["family"]
    drv = Program(cfg, fam, traffic, 7)
    snaps = [drv.setup_round() for _ in range(2)]
    work = drv.round_work(0, 2)
    theta0 = jax.tree.map(np.asarray, drv.theta0)
    ref = Reference(cfg, traffic, 7)
    recs = ref.run(theta0, 2)
    rows = oracle.eval_rows(len(ref.y), 8)
    numbers = oracle.compare(fam, cfg, theta0, snaps, recs, ref.x[rows],
                             ref.y[rows])
    return numbers, work, recs


def test_family_comes_from_config(monkeypatch):
    """With the cell's configuration naming another family, the program,
    the reference and the comparison call that family's every contract
    function and never seq_cnn's, and give the same numbers as seq_cnn."""
    want_numbers, want_work, want_recs = _two_rounds(
        tiny_cell("femnist_cnn.sync128_e5"))

    mod, calls = _counted_family("counted_cnn")
    monkeypatch.setitem(sys.modules, "bench.reference.counted_cnn", mod)

    def refuse(fn_name):
        def raiser(*a, **k):
            raise AssertionError(f"seq_cnn.{fn_name} called by name")
        return raiser
    for fn_name in CONTRACT:
        monkeypatch.setattr(seq_cnn, fn_name, refuse(fn_name))

    cell = tiny_cell("femnist_cnn.sync128_e5")
    cell["config"] = dict(cell["config"], family="counted_cnn")
    cell["family"] = mod
    numbers, work, recs = _two_rounds(cell)

    assert numbers == want_numbers
    assert work == want_work
    assert recs[1].sub_model_clients == want_recs[1].sub_model_clients == [0]
    for r, w in zip(recs, want_recs):
        for a, b in zip(jax.tree.leaves(r.params), jax.tree.leaves(w.params)):
            np.testing.assert_array_equal(a, b)
    assert sorted(fn for fn in CONTRACT if not calls[fn]) == []


# ---------------------------------------------------------------------------
# token rows: integer inputs and a label per position

V, T, D = 11, 5, 4


def _token_family():
    """A bigram language model over V tokens: each position's logits are
    its token's embedding against every embedding; a sequence's loss is
    its mean next-token cross-entropy. `seen` records the dtype and shape
    of every batch the loss is traced with."""
    seen = []

    def example_loss(cfg, params, x, y, precision):
        seen.append((x.dtype, x.shape, y.dtype, y.shape))
        e = params["emb"]
        logits = jnp.einsum("btd,vd->btv", e[x], e,
                            precision=precision).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - gold, axis=-1)
    return types.SimpleNamespace(example_loss=example_loss), seen


def _token_reference():
    """The tiny FEMNIST reference with its rows swapped for token rows:
    int32 ids (N, T) and their next tokens (N, T)."""
    cell = tiny_cell("femnist_cnn.sync128_e5")
    ref = Reference(cell["config"], cell["traffic"], 7)
    n = len(ref.y)
    toks = np.random.default_rng(3).integers(0, V, size=(n, T + 1),
                                             dtype=np.int32)
    ref.x, ref.y = toks[:, :-1].copy(), toks[:, 1:].copy()
    return ref


def test_batches_keep_token_rows():
    """A client's batches keep x's integer dtype and y's trailing shape and
    dtype, row for row."""
    ref = _token_reference()
    client = ref._clients[0]
    rng_state = client.rng.get_state()
    xs, ys, steps = ref._batches(client)
    bs = min(ref.bs, len(client.idx))
    assert (xs.dtype, ys.dtype) == (np.int32, np.int32)
    assert xs.shape == ys.shape == (ref.s_max, bs, T)
    client.rng.set_state(rng_state)
    order = client.rng.permutation(len(client.idx))[:steps * bs]
    rows = client.idx[order]
    np.testing.assert_array_equal(xs[:steps].reshape(-1, T), ref.x[rows])
    np.testing.assert_array_equal(ys[:steps].reshape(-1, T), ref.y[rows])


@pytest.mark.parametrize("variant", fluid_ref.VARIANTS)
def test_sgd_keeps_token_ids(variant):
    """Local SGD hands the family's loss the token ids as integers in every
    variant, the bfloat16 control included, and labels of shape (bs, T)."""
    ref = _token_reference()
    fam, seen = _token_family()
    train = fluid_ref._sgd_program(fam, ref.cfg, variant)
    xs, ys, steps = ref._batches(ref._clients[0])
    params = {"emb": np.random.default_rng(4).standard_normal(
        (V, D)).astype(np.float32)}
    delta = train(params, xs, ys, steps, np.float32(0.1))
    bs = xs.shape[1] // 2 if variant == "half_batch" else xs.shape[1]
    assert seen and all(s == (jnp.int32, (bs, T), jnp.int32, (bs, T))
                        for s in seen), seen
    assert delta["emb"].dtype == jnp.float32
    assert np.all(np.isfinite(delta["emb"]))
    assert np.abs(np.asarray(delta["emb"])).max() > 0
