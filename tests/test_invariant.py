"""Invariant-neuron statistics + threshold calibration (paper §4/§5)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="install the [dev] extra")
from hypothesis import given, settings, strategies as st

from repro.core import invariant as inv
from repro.core import spans

SPECS = [{"name": "g", "size": 8,
          "out": [("w", 1, 1), ("b", 0, 1)], "in": []}]


def _trees(delta_scale):
    rng = np.random.RandomState(0)
    w = rng.randn(6, 8).astype(np.float32)
    b = rng.randn(8).astype(np.float32)
    prev = {"w": jnp.asarray(w), "b": jnp.asarray(b)}
    d = np.zeros((6, 8), np.float32)
    d[:, :4] = delta_scale          # neurons 0-3 change, 4-7 invariant
    new = {"w": jnp.asarray(w + d), "b": jnp.asarray(b)}
    return prev, new


def test_stats_separate_changed_neurons():
    prev, new = _trees(0.5)
    s = inv.neuron_stats(prev, new, SPECS)["g"]
    assert np.all(np.asarray(s[:4]) > np.asarray(s[4:]).max())
    np.testing.assert_allclose(np.asarray(s[4:]), 0.0, atol=1e-7)


def test_norm_stat_value():
    prev, new = _trees(1.0)
    s = np.asarray(inv.neuron_stats(prev, new, SPECS)["g"])
    w = np.asarray(prev["w"])
    b = np.asarray(prev["b"])
    den = np.sqrt((w[:, 0] ** 2).sum() + b[0] ** 2)
    np.testing.assert_allclose(s[0], np.sqrt(6.0) / (den + 1e-8), rtol=1e-5)


def test_majority_vote():
    prev, new = _trees(0.5)
    quiet = inv.neuron_stats(prev, prev, SPECS)     # all zero
    loud = inv.neuron_stats(prev, new, SPECS)
    # 3 clients: 2 quiet, 1 loud -> all neurons invariant by majority
    m = inv.invariant_mask([quiet, quiet, loud], th=1e-6)
    assert m["g"].sum() == 8
    # 1 quiet, 2 loud -> only 4 neurons invariant for the majority
    m = inv.invariant_mask([quiet, loud, loud], th=1e-6)
    assert m["g"].sum() == 4


def test_threshold_calibration_monotone():
    prev, new = _trees(0.5)
    stats = [inv.neuron_stats(prev, new, SPECS)] * 3
    th0 = inv.initial_threshold(stats)
    th = inv.calibrate_threshold(stats, n_drop_target=6, th0=th0)
    assert th >= th0
    assert inv.count_invariant(stats, th) >= 6
    # higher target -> higher (or equal) threshold
    th2 = inv.calibrate_threshold(stats, n_drop_target=8, th0=th0)
    assert th2 >= th


@settings(max_examples=20, deadline=None)
@given(scale=st.floats(1e-3, 10.0), seed=st.integers(0, 1000))
def test_count_monotone_in_threshold(scale, seed):
    rng = np.random.RandomState(seed)
    w = rng.randn(5, 8).astype(np.float32)
    prev = {"w": jnp.asarray(w), "b": jnp.zeros(8)}
    new = {"w": jnp.asarray(w + scale * rng.randn(5, 8).astype(np.float32)),
           "b": jnp.zeros(8)}
    stats = [inv.neuron_stats(prev, new, SPECS)]
    ths = [1e-4, 1e-2, 1.0, 100.0]
    counts = [inv.count_invariant(stats, t) for t in ths]
    assert counts == sorted(counts)


# ---------------------------------------------------------------- oracle
# Per-client jnp versions of the vote and threshold functions: one device
# comparison per client and group, stacked on the device, as the host
# NumPy search must reproduce exactly.

def _jnp_counts(stats, th):
    return {g: np.asarray(jnp.stack([cs[g] <= th for cs in stats])
                          .sum(axis=0))
            for g in stats[0]}


def _jnp_mask(stats, th):
    return {g: c > len(stats) / 2 for g, c in _jnp_counts(stats, th).items()}


def _jnp_count(stats, th):
    return int(sum(v.sum() for v in _jnp_mask(stats, th).values()))


def _jnp_initial(stats):
    mins = [jnp.concatenate([v.ravel() for v in cs.values()]).min()
            for cs in stats]
    return float(jnp.mean(jnp.stack(mins)))


def _jnp_mean(stats):
    return {g: np.asarray(jnp.stack([cs[g] for cs in stats]).mean(axis=0))
            for g in stats[0]}


def _jnp_calibrate(stats, target, th0, max_iters=200):
    th, passes = max(float(th0), inv.EPS), 0
    for _ in range(max_iters):
        passes += 1
        if _jnp_count(stats, th) >= target:
            break
        th *= inv.TH_GROWTH
    return th, passes


GROUP_SIZES = {"conv1": 16, "conv2": 64, "fc1": 120}


def _client_stats(n, as_jax):
    """n clients' stats over three unit groups: log-spread positive values
    with exact zeros and values repeated across clients (ties)."""
    rng = np.random.RandomState(n)
    out = []
    for i in range(n):
        cs = {}
        for g, size in GROUP_SIZES.items():
            v = np.exp(rng.uniform(-12, 0, size)).astype(np.float32)
            v[rng.rand(size) < 0.05] = 0.0
            if i:
                tie = rng.rand(size) < 0.2
                v[tie] = np.asarray(out[0][g])[tie]
            cs[g] = jnp.asarray(v) if as_jax else v
        out.append(cs)
    return out


def _thresholds(stats):
    """A sweep: exact stat values, the float64 midpoints just below and
    above them (which round to float32 either way), and round numbers."""
    flat = np.sort(np.concatenate([np.asarray(v).ravel()
                                   for cs in stats for v in cs.values()]))
    picks = flat[np.linspace(0, flat.size - 1, 9).astype(int)]
    mids = [(float(p) + float(np.nextafter(p, np.float32(to)))) / 2
            for p in picks for to in (-np.inf, np.inf)]
    return ([float(p) for p in picks] + mids
            + [0.0, 1e-8, 1e-4, 1e-2, 0.1, 1.0, 1e30])


@pytest.mark.parametrize("as_jax", [False, True], ids=["numpy", "jax"])
@pytest.mark.parametrize("n", [1, 2, 115])
def test_host_search_matches_per_client_jnp(n, as_jax):
    stats = _client_stats(n, as_jax)
    for th in _thresholds(stats):
        want = _jnp_counts(stats, th)
        got = inv.invariant_counts(stats, th)
        assert set(got) == set(want)
        for g in want:
            np.testing.assert_array_equal(got[g], want[g])
            assert got[g].dtype == want[g].dtype
        got_m, want_m = inv.invariant_mask(stats, th), _jnp_mask(stats, th)
        for g in want_m:
            np.testing.assert_array_equal(got_m[g], want_m[g])
        assert inv.count_invariant(stats, th) == _jnp_count(stats, th)

    th0 = inv.initial_threshold(stats)
    assert th0 == pytest.approx(_jnp_initial(stats), rel=1e-6)
    total = sum(GROUP_SIZES.values())
    for target in (1, total // 3, total // 2, total):
        want_th, want_passes = _jnp_calibrate(stats, target, th0)
        with spans.record() as rec:
            got_th = inv.calibrate_threshold(stats, target, th0)
        assert got_th == want_th
        assert rec.counters["fluid.threshold_iters"] == want_passes

    got_mean, want_mean = inv.mean_stats(stats), _jnp_mean(stats)
    for g in want_mean:
        assert got_mean[g].dtype == np.float32
        np.testing.assert_allclose(got_mean[g], want_mean[g], rtol=1e-6)
