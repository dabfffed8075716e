"""Emulated FL clients.

Each client owns a non-IID data shard and a *speed model* calibrated to the
paper's measurement (App. A.3): end-to-end round time is linear in sub-model
size r, with multiplicative noise, plus a communication term proportional to
the transferred parameter count. Local training itself is real JAX SGD — the
deltas are genuine; only wall-clock is modeled (DESIGN.md §7.1). A client's
speed can be changed mid-run to emulate runtime variation (paper Fig. 4b).

Two execution paths share the same data/speed model:
  * SimClient.train — the sequential reference: one jit call per client,
    stragglers get a physically extracted sub-model (core/submodel.extract).
  * FleetClient — the batched path: exposes the epoch sample order and
    the time model so fl/fleet.py can run a whole cohort as one compiled
    program. Both consume the per-client RNG in the same order
    (local_epochs permutations, then one noise draw), so a fleet round is
    bit-identical to the sequential round in everything but float summation
    order.
"""
from __future__ import annotations

import math

from dataclasses import dataclass, field
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregate import ClientUpdate

_JIT_CACHE: Dict[str, callable] = {}


def make_loss(model_cls):
    """Mean softmax cross-entropy — shared by the sequential and fleet paths."""
    def loss(params, xb, yb):
        logits = model_cls.apply(params, xb)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - gold)
    return loss


def make_weighted_loss(model_cls):
    """Sample-weighted mean cross-entropy (fl/fleet.py batch padding).

    With weights 1 on a client's real samples and 0 on padding, this equals
    the client's own `mean` loss exactly, so cohorts whose shards are
    smaller than the global batch size still match the sequential path. An
    all-zero weight row (a padded *step*) yields a constant 0 loss, hence a
    zero gradient — a no-op SGD step."""
    def loss(params, xb, yb, wb):
        logits = model_cls.apply(params, xb)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return jnp.sum(wb * (lse - gold)) / jnp.maximum(wb.sum(), 1.0)
    return loss


def make_weighted_kernel_loss(model_cls):
    """make_weighted_loss routed through the model's Pallas path.

    Identical weighted cross-entropy, but the forward is
    `model_cls.apply_kernels(params, xb, kmasks)` — the masked-matmul route
    of models/kernel_models.py, where dropped 128-blocks/heads are skipped
    rather than multiplied by zero (DESIGN.md §10). `kmasks` is the small
    per-group mask dict from `model_cls.kernel_masks`."""
    def loss(params, xb, yb, wb, kmasks):
        logits = model_cls.apply_kernels(params, xb, kmasks)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return jnp.sum(wb * (lse - gold)) / jnp.maximum(wb.sum(), 1.0)
    return loss


def _train_fn(model_cls):
    key = model_cls.__name__
    if key not in _JIT_CACHE:
        loss = make_loss(model_cls)

        @jax.jit
        def run(params, xs, ys, lr):
            """xs: (nb, bs, ...) — one pass of minibatch SGD."""
            def step(p, batch):
                xb, yb = batch
                g = jax.grad(loss)(p, xb, yb)
                return jax.tree.map(lambda w, gw: w - lr * gw, p, g), 0
            params, _ = jax.lax.scan(step, params, (xs, ys))
            return params
        _JIT_CACHE[key] = run
    return _JIT_CACHE[key]


@dataclass
class SimClient:
    id: int
    model_cls: type
    x: np.ndarray
    y: np.ndarray
    speed: float                     # seconds per epoch at r = 1.0
    comm_s_per_mparam: float = 0.05  # transfer seconds per 1e6 params (x2)
    noise: float = 0.03
    tail_sigma: float = 0.0          # lognormal heavy-tail sigma (0 = off)
    batch_size: int = 20
    local_epochs: int = 1
    lr: float = 0.01
    seed: int = 0
    _rng: np.random.RandomState = field(init=False, repr=False)

    def __post_init__(self):
        # modulo keeps the derived seed in RandomState's [0, 2**32) domain:
        # capacity pads (fl/async_rounds.py) carry reserved negative ids,
        # and in-range values pass through unchanged, so every pre-existing
        # client stream is preserved bit-for-bit
        self._rng = np.random.RandomState((self.seed + 1000 * self.id)
                                          % (2 ** 32))

    @property
    def n_samples(self) -> int:
        return len(self.y)

    @property
    def eff_batch_size(self) -> int:
        return min(self.batch_size, self.n_samples)

    # ------------------------------------------------------------ speed model
    def _epoch_order(self) -> np.ndarray:
        """One epoch's minibatch sample order (consumes one RNG draw)."""
        bs = self.eff_batch_size
        nb = self.n_samples // bs
        return self._rng.permutation(self.n_samples)[:nb * bs]

    def _sim_time(self, rate: float, n_params: int) -> float:
        """End-to-end emulated seconds (consumes one RNG draw; a second
        when tail_sigma > 0): linear in sub-model size + transfer term
        (paper App. A.3). `tail_sigma` multiplies the compute time by a
        lognormal draw — the heavy-tailed straggler latencies of the async
        benchmark. It lives here, not in the async ArrivalModel, so the
        synchronous barrier baseline experiences the identical latency
        distribution; at 0.0 no extra draw is consumed, preserving every
        pre-existing seeded run bit-for-bit."""
        sim = (self.speed * self.local_epochs * rate
               * (1.0 + self.noise * self._rng.randn()))
        if self.tail_sigma > 0.0:
            sim *= math.exp(self.tail_sigma * float(self._rng.randn()))
        sim += 2 * self.comm_s_per_mparam * n_params / 1e6
        return max(sim, 1e-6)

    # ------------------------------------------------------------------ train
    def train(self, params, keep_map=None, rate: float = 1.0) -> ClientUpdate:
        run = _train_fn(self.model_cls)
        bs = self.eff_batch_size
        nb = self.n_samples // bs
        new_params = params
        for _ in range(self.local_epochs):
            order = self._epoch_order()
            xs = jnp.asarray(self.x[order].reshape(nb, bs, *self.x.shape[1:]))
            ys = jnp.asarray(self.y[order].reshape(nb, bs))
            new_params = run(new_params, xs, ys, self.lr)
        delta = jax.tree.map(lambda a, b: a - b, new_params, params)
        n_par = sum(x.size for x in jax.tree.leaves(params))
        sim = self._sim_time(rate, n_par)
        return ClientUpdate(delta, self.n_samples, None, sim, self.id)

    def evaluate(self, params, x=None, y=None):
        x = self.x if x is None else x
        y = self.y if y is None else y
        logits = self.model_cls.apply(params, jnp.asarray(x))
        return float((jnp.argmax(logits, -1) == jnp.asarray(y)).mean())


@dataclass
class FleetClient(SimClient):
    """Batched-path client: same shard, speed model, and RNG stream as
    SimClient, but training happens inside fl/fleet.py's cohort program,
    which maps the clients one at a time, instead of a per-client `train`
    call."""

    def local_orders(self) -> np.ndarray:
        """The round's (local_epochs * nb, bs) int32 sample order into this
        client's shard, batch by batch, consuming the RNG exactly like
        sequential train(): one epoch permutation per local epoch, in
        order."""
        bs = self.eff_batch_size
        nb = self.n_samples // bs
        orders = np.concatenate([self._epoch_order()
                                 for _ in range(self.local_epochs)])
        return orders.reshape(self.local_epochs * nb, bs).astype(np.int32)

    def draw_sim_time(self, rate: float, n_params: int) -> float:
        """The post-training noise draw, in SimClient.train's RNG order."""
        return self._sim_time(rate, n_params)
