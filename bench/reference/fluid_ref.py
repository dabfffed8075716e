"""Plain reference of FLuID's synchronous rounds (Algorithm 1), client by client.

Given a configuration, a traffic mix (cohort, data, stragglers) and a seed,
it takes the benchmark's own inputs for a fixed cohort
(bench/harness/data.py), or rebuilds a population's data, registry and
cohorts from the seeded generators the system under test documents, and
the clients' batch orders and latency draws from their documented seeded
streams. The model comes from the configuration's family
(bench/reference/<family>.py, whose docstring gives the contract): its
per-example loss, its unit groups, sub-model extraction, embedding and
unit statistics. Then it runs each round literally:

  1. every client of the cohort trains with minibatch SGD, one client at a
     time; a straggler trains the physically extracted sub-model that the
     previous calibration chose for it;
  2. the server takes the participation-weighted FedAvg, element by element
     over the clients that trained each weight;
  3. calibration: full-model-equivalent latencies pick the straggler band,
     the invariant-neuron statistics of the non-stragglers move the drop
     threshold, and the invariant policy chooses the units the next
     sub-models keep.

It imports nothing of the program. `variant` plants the two deviations its
comparison has to catch: "bf16" computes local SGD in bfloat16 (the control:
the precision step below the configuration's float32; integer inputs such
as token ids are left as they are), and "half_batch" leaves half of every
batch out and takes the mean over the rest.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.data import cohort_data, family_of

VARIANTS = ("reference", "bf16", "half_batch")
TH_GROWTH = 1.25
EMA_DECAY = 0.5


# ---------------------------------------------------------------------------
# seeded generators (the documented data, speed and cohort streams)

def image_data(shape, num_classes: int, n: int, n_writers: int, seed: int):
    """(x, y, writer) of the synthetic image set: class prototypes plus a
    writer style plus noise. Only the training split is drawn; the test
    split comes after it in the stream and is not needed. The population
    entry draws its data here, so it serves image families only."""
    rng = np.random.RandomState(seed)
    protos = rng.randn(num_classes, *shape).astype(np.float32)
    styles = 0.6 * rng.randn(n_writers, *shape).astype(np.float32)
    y = rng.randint(0, num_classes, size=n)
    w = rng.randint(0, n_writers, size=n)
    x = protos[y] + 1.2 * rng.randn(n, *shape).astype(np.float32)
    x = x + styles[w]
    return x, y, w


def partition_even(n: int, n_parts: int, seed: int):
    return np.array_split(np.random.RandomState(seed).permutation(n), n_parts)


def population_speeds(n: int, frac: float, slow_factor: float, seed: int,
                      base: float = 10.0) -> np.ndarray:
    rng = np.random.RandomState(seed)
    speeds = base * (1.0 + 0.05 * np.clip(rng.randn(n), -2.5, 2.5))
    slow = rng.rand(n) < frac
    speeds[slow] = base * slow_factor
    return speeds.astype(np.float32)


def sample_cohort(seed: int, rnd: int, n_clients: int, size: int):
    """Sorted ids of a Gumbel top-k draw over the whole registry."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), rnd)
    g = jax.random.gumbel(key, (n_clients,), jnp.float32)
    return np.sort(np.asarray(jax.lax.top_k(g, size)[1])).astype(np.int64)


# ---------------------------------------------------------------------------
# straggler band and invariant policy

def detect_band(lat: Dict[int, float], gap_factor: float = 1.10) -> List[int]:
    """Slow band over sorted latencies: the two-means cut, else the largest
    adjacent difference, accepted when the band's median is gap_factor
    above the fast side's; slowest first."""
    if len(lat) < 3:
        ids = sorted(lat, key=lambda c: lat[c], reverse=True)
        if len(ids) < 2:
            return []
        ratios = [lat[ids[i]] / max(lat[ids[i + 1]], 1e-12)
                  for i in range(len(ids) - 1)]
        g = max(range(len(ratios)), key=ratios.__getitem__)
        return ids[:g + 1] if ratios[g] > gap_factor else []
    ids = sorted(lat, key=lat.__getitem__)
    x = np.asarray([lat[c] for c in ids], np.float64)
    n = x.size

    def accept(cut):
        ref = float(np.median(x[:cut]))
        if not float(np.median(x[cut:])) > gap_factor * ref:
            return None
        return [c for c in reversed(ids[cut:])
                if lat[c] > gap_factor * ref] or None

    cs, css = np.cumsum(x), np.cumsum(x * x)
    k = np.arange(1, n)
    s0, ss0 = cs[:-1], css[:-1]
    s1, ss1 = cs[-1] - s0, css[-1] - ss0
    within = (ss0 - s0 * s0 / k) + (ss1 - s1 * s1 / (n - k))
    band = accept(int(np.argmin(within)) + 1)
    if band is None:
        band = accept(int(np.argmax(np.diff(x))) + 1)
    return band or []


def _votes(stats: List[Dict[str, np.ndarray]], th: float):
    return {g: np.stack([s[g] <= th for s in stats]).sum(axis=0)
            for g in stats[0]}


def _n_invariant(stats, th: float) -> int:
    n = len(stats)
    return int(sum((v > n / 2).sum() for v in _votes(stats, th).values()))


@dataclass
class InvariantPolicy:
    """Threshold calibration and the EMA-ranked choice of dropped units
    over the unit groups of the configuration's family."""
    cfg: dict
    family: object
    th: Optional[float] = None
    votes: Optional[Dict[str, np.ndarray]] = None
    means: Optional[Dict[str, np.ndarray]] = None

    def calibrate(self, stats, rate: float):
        if self.th is None:
            mins = np.asarray([np.concatenate([v.ravel() for v in s.values()])
                               .min() for s in stats], np.float32)
            self.th = float(np.mean(mins))
        fam = self.family
        target = sum(size - fam.keep_count(size, rate)
                     for _, size in fam.droppable(self.cfg))
        if target:
            th = max(float(self.th), 1e-8)
            for _ in range(200):
                if _n_invariant(stats, th) >= target:
                    break
                th *= TH_GROWTH
            self.th = th
        votes = _votes(stats, self.th)
        means = {g: np.stack([s[g] for s in stats]).mean(axis=0)
                 for g in stats[0]}
        if self.votes is None:
            self.votes = {g: v.astype(np.float64) for g, v in votes.items()}
            self.means = means
        else:
            a = EMA_DECAY
            self.votes = {g: a * self.votes[g] + (1 - a) * votes[g]
                          for g in votes}
            self.means = {g: a * self.means[g] + (1 - a) * means[g]
                          for g in means}

    def keep_map(self, rate: float) -> Dict[str, np.ndarray]:
        out = {}
        for name, size in self.family.droppable(self.cfg):
            k = self.family.keep_count(size, rate)
            if self.votes is None:
                out[name] = np.arange(k)
                continue
            order = np.lexsort((self.means[name], -self.votes[name]))
            out[name] = np.sort(np.setdiff1d(np.arange(size),
                                             order[:size - k]))
        return out


# ---------------------------------------------------------------------------
# local SGD

def _sgd_program(family, cfg, variant: str):
    dtype = jnp.bfloat16 if variant == "bf16" else jnp.float32
    precision = (jax.lax.Precision.DEFAULT if variant == "bf16"
                 else jax.lax.Precision.HIGHEST)
    half = variant == "half_batch"

    def loss(p, xb, yb):
        return jnp.mean(family.example_loss(cfg, p, xb, yb, precision))

    @jax.jit
    def train(params, xs, ys, n_steps, lr):
        """n_steps SGD steps over xs[:n_steps]; returns the float32 delta."""
        p0 = jax.tree.map(lambda a: a.astype(dtype), params)

        def step(i, p):
            xb, yb = xs[i], ys[i]
            if jnp.issubdtype(xb.dtype, jnp.floating):
                xb = xb.astype(dtype)
            if half:
                xb, yb = xb[:xb.shape[0] // 2], yb[:yb.shape[0] // 2]
            g = jax.grad(loss)(p, xb, yb)
            return jax.tree.map(lambda w, gw: (w - lr.astype(dtype) * gw)
                                .astype(dtype), p, g)
        p1 = jax.lax.fori_loop(0, n_steps, step, p0)
        return jax.tree.map(lambda a, b: a.astype(jnp.float32)
                            - b.astype(jnp.float32), p1, p0)
    return train


# ---------------------------------------------------------------------------
# the rounds

@dataclass
class RoundRecord:
    params: dict                     # global params after the round (host)
    cohort: List[int]
    sub_model_clients: List[int]     # trained a sub-model this round
    stragglers: List[int]            # the plan this round's calibration made
    keep_next: Dict[str, np.ndarray]  # units a straggler keeps next round


@dataclass
class _Client:
    cid: int
    idx: np.ndarray                  # rows of the data set
    speed: float
    rng: np.random.RandomState


@dataclass
class Reference:
    cfg: dict
    traffic: dict
    seed: int
    variant: str = "reference"
    _rates: Dict[int, float] = field(default_factory=dict)

    def __post_init__(self):
        if self.variant not in VARIANTS:
            raise ValueError(f"variant must be one of {VARIANTS}")
        t, cfg = self.traffic, self.cfg
        self.family = family_of(cfg)
        ds = cfg["dataset"]
        self.lr = float(cfg["lr"])
        self.bs = int(cfg["batch_size"])
        self.epochs = int(t["local_epochs"])
        if t["entry"] == "cohort":
            self.x, self.y, parts, speeds = cohort_data(cfg, t, self.seed)
            self._clients = [_Client(i, p, float(speeds[i]),
                                     np.random.RandomState(
                                         (self.seed + 1000 * i) % 2 ** 32))
                             for i, p in enumerate(parts)]
            self.s_max = max(self.epochs * (len(p) // min(self.bs, len(p)))
                             for p in parts)
        else:
            n_parts = int(t["n_partitions"])
            n_data = n_parts * int(t["samples_per_partition"])
            self.x, y, _ = image_data(
                tuple(cfg["input_shape"]), cfg["num_classes"], n_data,
                n_parts, self.seed + ds["seed_offset"])
            self.y = y.astype(np.int32)
            self._parts = partition_even(n_data, n_parts, self.seed)
            n = int(t["n_clients"])
            self._speeds = population_speeds(n, t["straggler_frac_pop"],
                                             t["slow_factor"], self.seed)
            self._shards = np.random.RandomState(self.seed + 1).randint(
                0, n_parts, size=n)
            spp = int(t["samples_per_partition"])
            self.s_max = self.epochs * (spp // min(self.bs, spp))
        self.policy = InvariantPolicy(cfg, self.family)
        self._train = _sgd_program(self.family, cfg, self.variant)

    def _cohort(self, rnd: int) -> List[_Client]:
        t = self.traffic
        if t["entry"] == "cohort":
            return self._clients
        ids = sample_cohort(self.seed, rnd, int(t["n_clients"]),
                            int(t["cohort_size"]))
        seed_r = self.seed + 65537 * rnd
        return [_Client(int(c), self._parts[self._shards[c]],
                        float(self._speeds[c]),
                        np.random.RandomState((seed_r + 1000 * int(c))
                                              % 2 ** 32))
                for c in ids]

    def _batches(self, c: _Client):
        n = len(c.idx)
        bs = min(self.bs, n)
        nb = n // bs
        orders = [c.rng.permutation(n)[:nb * bs] for _ in range(self.epochs)]
        rows = c.idx[np.concatenate(orders)]
        steps = self.epochs * nb
        xs = np.zeros((self.s_max, bs) + self.x.shape[1:], self.x.dtype)
        ys = np.zeros((self.s_max, bs) + self.y.shape[1:], self.y.dtype)
        xs[:steps] = self.x[rows].reshape(steps, bs, *self.x.shape[1:])
        ys[:steps] = self.y[rows].reshape(steps, bs, *self.y.shape[1:])
        return xs, ys, steps

    def run(self, params, rounds: int) -> List[RoundRecord]:
        cfg, t, fam = self.cfg, self.traffic, self.family
        rate_sub = float(t["fixed_rate"])
        params = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
        out = []
        for rnd in range(rounds):
            cohort = self._cohort(rnd)
            rates = {c.cid: (self._rates.get(c.cid, 1.0) if rnd >= 1 else 1.0)
                     for c in cohort}
            keep = self.policy.keep_map(rate_sub)
            num = jax.tree.map(np.zeros_like, params)
            den = jax.tree.map(np.zeros_like, params)
            lat, stats = {}, []
            for c in cohort:
                xs, ys, steps = self._batches(c)
                r = rates[c.cid]
                start = (params if r >= 1.0
                         else fam.extract(cfg, params, keep))
                d = self._train(start, xs, ys, steps, np.float32(self.lr))
                d = jax.tree.map(np.asarray, d)
                if r >= 1.0:
                    mask = jax.tree.map(np.ones_like, params)
                    new = jax.tree.map(np.add, params, d)
                    stats.append(fam.unit_stats(cfg, params, new))
                else:
                    d, mask = fam.embed(cfg, params, d, keep)
                n_par = sum(a.size for a in jax.tree.leaves(start))
                w = np.float32(len(c.idx))
                num = jax.tree.map(lambda a, dd, m: a + w * m * dd,
                                   num, d, mask)
                den = jax.tree.map(lambda a, m: a + w * m, den, mask)
                sim = (c.speed * self.epochs * r
                       * (1.0 + 0.03 * c.rng.randn()))
                sim = max(sim + 2 * 0.05 * n_par / 1e6, 1e-6)
                lat[c.cid] = float(np.float32(sim / r))
            params = jax.tree.map(
                lambda p, nm, dn: p + np.where(dn > 0, nm / np.maximum(
                    dn, 1e-12), 0.0).astype(np.float32), params, num, den)
            stragglers: List[int] = []
            if stats:
                stragglers = detect_band(lat)
                self.policy.calibrate(stats, rate_sub)
                for c in cohort:
                    self._rates[c.cid] = (rate_sub if c.cid in stragglers
                                          else 1.0)
            out.append(RoundRecord(
                params=params, cohort=[c.cid for c in cohort],
                sub_model_clients=[c.cid for c in cohort
                                   if rates[c.cid] < 1.0],
                stragglers=list(stragglers),
                keep_next=self.policy.keep_map(rate_sub)))
        return out

