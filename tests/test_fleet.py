"""Vectorized fleet engine == sequential reference path (fl/fleet.py).

The acceptance contract: with the same seeds, one compiled cohort round
reproduces the per-client sequential round — deltas (full-model AND
masked-straggler clients), emulated times, and the aggregated params — up
to float summation order; and the fused device-side aggregation matches
core.aggregate.aggregate.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import spans
from repro.core import submodel as sub
from repro.core.aggregate import ClientUpdate, aggregate, aggregate_stacked
from repro.core.dropout import DropoutPolicy
from repro.fl.client import FleetClient, SimClient
from repro.fl.fleet import FleetEngine, _cohort_fn
from repro.fl.simulation import (CohortConfig, SimulationConfig,
                                 build_simulation)


def _tree_close(a, b, atol, rtol=1e-5):
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), atol=atol, rtol=rtol), a, b)


def _cfg(backend):
    return SimulationConfig(
        workload="femnist", backend=backend, policy="invariant", seed=0,
        cohort=CohortConfig(n_clients=4, straggler_ids=(0,), n_data=240))


@pytest.fixture(scope="module")
def fleet_sim():
    return build_simulation(_cfg("fleet"))


def _clone_seq_client(c, model_cls):
    return SimClient(c.id, model_cls, c.x, c.y, speed=c.speed,
                     batch_size=c.batch_size, local_epochs=c.local_epochs,
                     lr=c.lr, seed=c.seed)


def test_full_cohort_deltas_match_sequential(fleet_sim):
    engine = fleet_sim.server.engine
    params = fleet_sim.server.params
    seq = [_clone_seq_client(c, fleet_sim.model_cls)
           for c in engine.clients]
    # fresh fleet clients so both paths draw the same RNG stream
    flt = [FleetClient(c.id, fleet_sim.model_cls, c.x, c.y, speed=c.speed,
                       batch_size=c.batch_size, local_epochs=c.local_epochs,
                       lr=c.lr, seed=c.seed) for c in engine.clients]
    eng = FleetEngine(fleet_sim.model_cls, flt, engine.unit_specs)
    cohort = eng.run_cohort(params, {})
    updates = cohort.updates()
    for c, u in zip(seq, updates):
        ref = c.train(params)
        assert u.client_id == ref.client_id
        assert u.sim_time == pytest.approx(ref.sim_time, rel=1e-12)
        _tree_close(u.delta, ref.delta, atol=2e-5)


def test_masked_straggler_delta_matches_extracted_submodel(fleet_sim):
    engine = fleet_sim.server.engine
    params = fleet_sim.server.params
    policy = DropoutPolicy("ordered", engine.unit_specs, seed=0)
    keep = policy.keep_map(0.5)
    c0 = engine.clients[0]
    seq = _clone_seq_client(c0, fleet_sim.model_cls)
    flt = [FleetClient(c.id, fleet_sim.model_cls, c.x, c.y, speed=c.speed,
                       batch_size=c.batch_size, local_epochs=c.local_epochs,
                       lr=c.lr, seed=c.seed) for c in engine.clients]
    eng = FleetEngine(fleet_sim.model_cls, flt, engine.unit_specs)
    cohort = eng.run_cohort(params, {0: keep}, {0: 0.5})
    u = cohort.updates()[0]
    # sequential reference: physically extracted sub-model + re-embedding
    sub_params = sub.extract(params, engine.unit_specs, keep)
    ref = seq.train(sub_params, keep_map=keep, rate=0.5)
    full_delta, mask = sub.embed_delta(ref.delta, params,
                                       engine.unit_specs, keep)
    assert u.sim_time == pytest.approx(ref.sim_time, rel=1e-12)
    _tree_close(u.mask, mask, atol=0)
    _tree_close(u.delta, full_delta, atol=2e-5)
    # fleet deltas come back already mask-zeroed
    jax.tree.map(lambda d, m: np.testing.assert_array_equal(
        np.asarray(d) * (1 - np.asarray(m)), 0.0), u.delta, u.mask)


def test_device_aggregation_matches_reference(fleet_sim):
    engine = fleet_sim.server.engine
    params = fleet_sim.server.params
    policy = DropoutPolicy("ordered", engine.unit_specs, seed=0)
    keep = policy.keep_map(0.65)
    cohort = engine.run_cohort(params, {1: keep}, {1: 0.65})
    got = cohort.aggregate(params)
    want = aggregate(params, cohort.updates())
    _tree_close(got, want, atol=1e-5)


def test_aggregate_stacked_pure_tree():
    """aggregate_stacked == aggregate on a hand-built masked cohort."""
    rng = np.random.RandomState(0)
    p = {"a": jnp.asarray(rng.randn(4, 3).astype(np.float32)),
         "b": jnp.asarray(rng.randn(5).astype(np.float32))}
    mask = {"a": jnp.asarray((rng.rand(4, 3) > 0.5).astype(np.float32)),
            "b": jnp.asarray((rng.rand(5) > 0.5).astype(np.float32))}
    ones = jax.tree.map(lambda x: jnp.ones_like(x), p)
    deltas = [jax.tree.map(lambda x: jnp.asarray(
        rng.randn(*x.shape).astype(np.float32)), p) for _ in range(3)]
    deltas[2] = jax.tree.map(lambda d, m: d * m, deltas[2], mask)
    weights = [2.0, 5.0, 3.0]
    updates = [ClientUpdate(deltas[0], 2, None, client_id=0),
               ClientUpdate(deltas[1], 5, None, client_id=1),
               ClientUpdate(deltas[2], 3, mask, client_id=2)]
    want = aggregate(p, updates)
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *deltas)
    bank = jax.tree.map(lambda a, b: jnp.stack([a, b]), ones, mask)
    got = aggregate_stacked(p, stacked, jnp.asarray(weights),
                            bank, jnp.asarray([0, 0, 1], jnp.int32))
    _tree_close(got, want, atol=1e-6)


def test_mask_bank_dedupes_identical_keep_maps(fleet_sim):
    """Two stragglers with the same keep-map share one bank row: K = 2
    (ones + 1 distinct), not 1 + n_stragglers."""
    engine = fleet_sim.server.engine
    params = fleet_sim.server.params
    policy = DropoutPolicy("ordered", engine.unit_specs, seed=0)
    keep = policy.keep_map(0.5)
    keep2 = {g: v.copy() for g, v in keep.items()}
    cohort = engine.run_cohort(params, {0: keep, 1: keep2},
                               {0: 0.5, 1: 0.5})
    assert jax.tree.leaves(cohort.mask_bank)[0].shape[0] == 2
    assert int(cohort.mask_idx[0]) == 1 and int(cohort.mask_idx[1]) == 1


def test_keep_mask_matches_embed_delta_mask(fleet_sim):
    engine = fleet_sim.server.engine
    params = fleet_sim.server.params
    policy = DropoutPolicy("random", engine.unit_specs, seed=3)
    keep = policy.keep_map(0.75)
    m = sub.keep_mask(params, engine.unit_specs, keep)
    zero_sub = jax.tree.map(jnp.zeros_like,
                            sub.extract(params, engine.unit_specs, keep))
    _, m_ref = sub.embed_delta(zero_sub, params, engine.unit_specs, keep)
    _tree_close(m, m_ref, atol=0)
    n_sub, _ = sub.submodel_sizes(params, engine.unit_specs, keep)
    total = sum(float(x.sum()) for x in jax.tree.leaves(m))
    assert int(total) == n_sub


def test_end_to_end_fleet_matches_sequential_rounds(fleet_sim):
    seq = build_simulation(_cfg("sequential"))
    flt = build_simulation(_cfg("fleet"))
    hs = seq.server.run(3)
    hf = flt.server.run(3)
    for a, b in zip(hs, hf):
        assert a.round_time == pytest.approx(b.round_time, rel=1e-9)
        assert a.stragglers == b.stragglers
        assert a.rates == b.rates
    _tree_close(seq.server.params, flt.server.params, atol=5e-4)


def test_heterogeneous_lr_and_epochs_match_sequential(fleet_sim):
    """Per-client (lr, local_epochs) are vmapped data: a mixed cohort still
    reproduces each client's own sequential run."""
    model_cls = fleet_sim.model_cls
    src = fleet_sim.server.engine.clients
    lrs = [0.004, 0.012, 0.002]
    epochs = [1, 2, 1]
    seq, flt = [], []
    for cid in range(3):
        c = src[cid]
        kw = dict(speed=1.0, batch_size=c.batch_size, lr=lrs[cid],
                  local_epochs=epochs[cid], seed=7)
        seq.append(SimClient(cid, model_cls, c.x, c.y, **kw))
        flt.append(FleetClient(cid, model_cls, c.x, c.y, **kw))
    params = fleet_sim.server.params
    eng = FleetEngine(model_cls, flt, fleet_sim.server.engine.unit_specs)
    cohort = eng.run_cohort(params, {})
    for c, u in zip(seq, cohort.updates()):
        ref = c.train(params)
        assert u.sim_time == pytest.approx(ref.sim_time, rel=1e-12)
        _tree_close(u.delta, ref.delta, atol=2e-5)


def test_lr_override_uniform_equivalence(fleet_sim):
    """run_cohort(lr=scalar) == a cohort whose clients all carry that lr,
    and a (C,)-array override with identical entries matches the scalar."""
    engine = fleet_sim.server.engine
    params = fleet_sim.server.params

    def fresh():
        return FleetEngine(fleet_sim.model_cls, [
            FleetClient(c.id, fleet_sim.model_cls, c.x, c.y, speed=c.speed,
                        batch_size=c.batch_size, local_epochs=c.local_epochs,
                        lr=c.lr, seed=c.seed) for c in engine.clients],
            engine.unit_specs)
    a = fresh().run_cohort(params, {}, lr=0.009)
    b = fresh().run_cohort(params, {},
                           lr=np.full(len(engine.clients), 0.009))
    _tree_close(a.deltas, b.deltas, atol=0)


def test_n_steps_override_caps_local_steps(fleet_sim):
    """n_steps zero-weights the tail: capping client 0 to one step equals a
    client that only had one batch worth of local SGD."""
    engine = fleet_sim.server.engine
    params = fleet_sim.server.params
    clients = [FleetClient(c.id, fleet_sim.model_cls, c.x, c.y, speed=c.speed,
                           batch_size=c.batch_size,
                           local_epochs=c.local_epochs, lr=c.lr, seed=c.seed)
               for c in engine.clients]
    eng = FleetEngine(fleet_sim.model_cls, clients, engine.unit_specs)
    caps = eng.client_steps.copy()
    caps[0] = 1
    cohort = eng.run_cohort(params, {}, n_steps=caps)
    # reference: client 0 truncated to one batch (same RNG permutation)
    c0 = engine.clients[0]
    ref_c = SimClient(0, fleet_sim.model_cls, c0.x, c0.y, speed=c0.speed,
                      batch_size=c0.batch_size, local_epochs=c0.local_epochs,
                      lr=c0.lr, seed=c0.seed)
    order = ref_c._epoch_order()
    bs = ref_c.eff_batch_size
    import jax.numpy as jnp2
    from repro.fl.client import _train_fn
    run = _train_fn(fleet_sim.model_cls)
    xs = jnp2.asarray(c0.x[order[:bs]][None])
    ys = jnp2.asarray(c0.y[order[:bs]][None])
    new_p = run(params, xs, ys, c0.lr)
    want = jax.tree.map(lambda a_, b_: a_ - b_, new_p, params)
    got = jax.tree.map(lambda d: d[0], cohort.deltas)
    _tree_close(got, want, atol=2e-5)
    with pytest.raises(ValueError, match="n_steps"):
        eng.run_cohort(params, {}, n_steps=np.array([1]))


def test_ragged_shards_match_sequential(fleet_sim):
    """Clients whose shards are smaller than the batch size (and of unequal
    step counts) still reproduce the sequential path via batch padding +
    per-sample loss weights."""
    model_cls = fleet_sim.model_cls
    src = fleet_sim.server.engine.clients
    sizes = [7, 23, 40]     # all below/above the batch size of 10
    seq, flt = [], []
    for cid, n in enumerate(sizes):
        c = src[0]
        kw = dict(speed=1.0, batch_size=10, lr=c.lr, seed=5)
        seq.append(SimClient(cid, model_cls, c.x[:n], c.y[:n], **kw))
        flt.append(FleetClient(cid, model_cls, c.x[:n], c.y[:n], **kw))
    params = fleet_sim.server.params
    eng = FleetEngine(model_cls, flt, fleet_sim.server.engine.unit_specs)
    cohort = eng.run_cohort(params, {})
    for c, u in zip(seq, cohort.updates()):
        ref = c.train(params)
        assert u.sim_time == pytest.approx(ref.sim_time, rel=1e-12)
        _tree_close(u.delta, ref.delta, atol=2e-5)


def _host_staged(clients, steps, bs, n_steps):
    """The round's batches as the host built them before the clients'
    samples lived on the device: each client's epoch permutations, its
    samples taken as x[orders], padded to (steps, bs)."""
    feat = clients[0].x.shape[1:]
    xs = np.zeros((len(clients), steps, bs, *feat), np.float32)
    ys = np.zeros((len(clients), steps, bs), np.int32)
    sw = np.zeros((len(clients), steps, bs), np.float32)
    for i, c in enumerate(clients):
        b = c.eff_batch_size
        orders = np.concatenate([c._epoch_order()
                                 for _ in range(c.local_epochs)])
        x = c.x[orders].reshape(-1, b, *feat)
        s = x.shape[0]
        xs[i, :s, :b] = x
        ys[i, :s, :b] = c.y[orders].reshape(s, b)
        sw[i, :s, :b] = 1.0
        sw[i, int(n_steps[i]):] = 0.0
    return xs, ys, sw


def test_gathered_batches_match_host_batches(fleet_sim):
    """The batches the cohort program gathers on the device from the
    clients' resident samples are the batches the host used to build, with
    the same labels and sample weights, and the round leaves every client's
    RNG where the host-built round left it. Shards of different sizes and
    batch sizes, a step cap and a padding slot."""
    model_cls = fleet_sim.model_cls
    src = fleet_sim.server.engine.clients[0]
    shape = [(7, 10, 1), (23, 10, 2), (40, 8, 1), (31, 5, 1)]

    def make():
        return [FleetClient(cid, model_cls, src.x[:n], src.y[:n], speed=1.0,
                            batch_size=bs, local_epochs=ep, lr=src.lr,
                            seed=11)
                for cid, (n, bs, ep) in enumerate(shape)]
    eng, old = FleetEngine(model_cls, make(), fleet_sim.server.engine
                           .unit_specs), make()
    caps = eng.client_steps.copy()
    caps[1] = 3
    members = np.array([True, True, False, True])
    staged = []

    def spy(n_steps=None):
        staged.append(FleetEngine._stacked_data(eng, n_steps))
        return staged[-1]
    eng._stacked_data = spy
    params = fleet_sim.server.params
    res = eng.run_cohort(params, {}, n_steps=caps, members=members)
    (order, ys, sw), = staged

    eff = np.where(members, caps, 0)
    want_x, want_y, want_sw = _host_staged(old, eng.steps, eng.bs, eff)
    got_x = np.asarray(jax.jit(jax.vmap(lambda cx, o: cx[o]))(
        eng._client_data(), order))
    assert got_x.shape == want_x.shape
    for i, c in enumerate(old):
        s, b = c.local_epochs * (c.n_samples // c.eff_batch_size), \
            c.eff_batch_size
        np.testing.assert_array_equal(got_x[i, :s, :b], want_x[i, :s, :b])
    np.testing.assert_array_equal(np.asarray(ys), want_y)
    np.testing.assert_array_equal(np.asarray(sw), want_sw)
    n_par = sum(p.size for p in jax.tree.leaves(params))
    for i, (c, o) in enumerate(zip(eng.clients, old)):
        if members[i]:
            assert res.sim_times[c.id] == o.draw_sim_time(1.0, n_par)
        a, b = c._rng.get_state(), o._rng.get_state()
        np.testing.assert_array_equal(a[1], b[1])
        assert a[2:] == b[2:]


def test_non_straggler_stats_rows_match_per_client_copies(fleet_sim):
    """The stacked stats come to the host one copy per unit group, and the
    rows equal a per-client copy of each client's slice, in cohort order,
    without the straggler or the padding slot."""
    engine = fleet_sim.server.engine
    params = fleet_sim.server.params
    keep = DropoutPolicy("ordered", engine.unit_specs, seed=0).keep_map(0.5)
    ids = [c.id for c in engine.clients]
    members = np.array([True, True, False, True])
    cohort = engine.run_cohort(params, {ids[1]: keep}, {ids[1]: 0.5},
                               members=members)
    with spans.record() as rec:
        rows = cohort.non_straggler_stats(params)
    assert rec.counters["fleet.stats_copies"] == len(engine.unit_specs)

    sel = [i for i, cid in enumerate(ids) if cid != ids[1] and members[i]]
    picked = jax.tree.map(lambda d: d[np.asarray(sel)], cohort.deltas)
    stacked = engine._stats_fn(params, picked)
    want = [{g: np.asarray(v[i]) for g, v in stacked.items()}
            for i in range(len(sel))]
    assert len(rows) == len(want) == 2
    for got_row, want_row in zip(rows, want):
        assert list(got_row) == list(want_row)
        for g in want_row:
            assert got_row[g].dtype == np.float32
            np.testing.assert_array_equal(got_row[g], want_row[g])


def _lowered_cohort_text(model):
    """The cohort program of `model`, lowered at a tiny cohort."""
    from repro.models.small import MODELS
    model_cls = MODELS[model]
    c, s, b, rows = 3, 2, 2, 6
    sd = jax.ShapeDtypeStruct
    params = jax.eval_shape(model_cls.init, jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: sd(a.shape, jnp.float32), params)
    bank = jax.tree.map(lambda a: sd((2,) + a.shape, jnp.float32), params)
    return _cohort_fn(model_cls).lower(
        p, bank, sd((c,), jnp.int32),
        sd((c, rows) + model_cls.input_shape, jnp.float32),
        sd((c, s, b), jnp.int32),
        sd((c, s, b), jnp.int32), sd((c, s, b), jnp.float32),
        sd((c,), jnp.float32), n_steps=s).as_text()


@pytest.mark.parametrize("model", ["femnist_cnn", "cifar_vgg9"])
def test_cohort_program_has_no_grouped_convolutions(model):
    """Each client's convolutions run at the client's own shapes. Batched
    over the cohort (jax.vmap), per-client weights turn every convolution
    into one grouped over the clients, and a TPU v5e at default precision
    computes some of those wrong (the VGG-9's c1a, c3a and c3b weight
    gradients at 64 clients). The lowered program is checked here on the
    CPU; the wrong numbers themselves show only on the chip."""
    import re
    text = _lowered_cohort_text(model)
    assert "stablehlo.convolution" in text
    groups = re.findall(r"(?:feature|batch)_group_count = (\d+)", text)
    assert groups and set(groups) == {"1"}, sorted(set(groups))


@pytest.mark.parametrize("model", ["femnist_cnn", "cifar_vgg9"])
def test_cohort_program_has_no_select_and_scatter(model):
    """The max-pools' gradient is a selection of elementwise ops
    (models/small._pool). reduce_window's own gradient lowers to a
    select_and_scatter, which a TPU v5e runs as a windowed scatter at about
    twice the time of an elementwise pass over the same tensor."""
    text = _lowered_cohort_text(model)
    assert "stablehlo.convolution" in text
    assert "select_and_scatter" not in text
