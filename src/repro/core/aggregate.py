"""Masked FedAvg aggregation (paper Algorithm 1, line 16).

Clients return deltas (new - broadcast). Stragglers' deltas arrive embedded
in full coordinates with a participation mask. The server averages each
element over the clients that actually trained it, weighted by sample count:

    w_new = w + sum_c(n_c * mask_c * delta_c) / sum_c(n_c * mask_c)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp


@dataclass
class ClientUpdate:
    delta: dict                 # full-coordinate delta tree
    n_samples: int
    mask: Optional[dict] = None  # None = trained the full model
    sim_time: float = 0.0
    client_id: int = -1


def partial_sums(stacked_deltas, weights, mask_idx, num_masks: int):
    """Shard-local half of the masked FedAvg (hierarchical aggregation).

    Reduces one shard's (Cs, ...) stacked deltas to the two sufficient
    statistics of `aggregate_stacked`:

        num        = sum_c w_c * delta_c            (tree of param-shaped leaves)
        w_per_mask = sum_{c: idx_c=k} w_c           ((K,) float32)

    Because both are plain sums over clients, per-shard partials add across
    shards (and `jax.lax.psum` across devices) to exactly the cohort-level
    statistics — the MaskBank stays replicated, so the denominator
    `sum_k w_per_mask_k * bank_k` is reconstructed after the reduction by
    `combine_partials`. num_masks must be the bank's row count K (static).
    """
    weights = weights.astype(jnp.float32)
    w_per_mask = jax.ops.segment_sum(weights, mask_idx,
                                     num_segments=num_masks)
    num = jax.tree.map(
        lambda d: jnp.tensordot(weights, d.astype(jnp.float32), axes=1),
        stacked_deltas)
    return num, w_per_mask


def combine_partials(global_params, num, w_per_mask, mask_bank):
    """Apply fully-reduced `partial_sums` statistics to the global params:

        w_new = w + num / (sum_k w_per_mask_k * bank_k)   where den > 0.

    The (num, w_per_mask) pair is linear in the clients, so any reduction
    tree over shard partials (sequential adds, psum, …) yields the same
    inputs here up to float summation order.
    """
    den = jax.tree.map(lambda b: jnp.tensordot(w_per_mask, b, axes=1),
                       mask_bank)
    return jax.tree.map(
        lambda p, n, d: p + jnp.where(d > 0, n / jnp.maximum(d, 1e-12),
                                      0.0).astype(p.dtype),
        global_params, num, den)


@jax.jit
def aggregate_stacked(global_params, stacked_deltas, weights,
                      mask_bank, mask_idx):
    """Fused device-side FedAvg over a stacked cohort (fl/fleet.py).

    stacked_deltas: tree of (C, ...) leaves, already mask-zeroed where a
    client did not train (so ``mask_c * delta_c == delta_c``).
    weights: (C,) sample counts. mask_bank: tree of (K, ...) distinct
    participation masks; mask_idx: (C,) int32 mapping client -> bank row
    (row of all-ones for full-model clients).

    Same formula as `aggregate` — the numerator collapses to one weighted
    tree-reduce because the deltas are pre-zeroed, and the denominator
    factors through the (few) distinct masks:
        num = sum_c w_c * delta_c
        den = sum_k (sum_{c: idx_c=k} w_c) * bank_k

    Expressed as the one-shard case of the hierarchical pipeline:
    `partial_sums` over the whole cohort, then `combine_partials` — the
    sharded executor (fl/shard_fleet.py) runs the same two functions with a
    psum in between.
    """
    k = jax.tree.leaves(mask_bank)[0].shape[0]
    num, w_per_mask = partial_sums(stacked_deltas, weights, mask_idx, k)
    return combine_partials(global_params, num, w_per_mask, mask_bank)


def staleness_scale(staleness, exponent):
    """Per-arrival staleness discount for buffered async FedAvg, normalized
    so a uniformly-stale buffer degenerates to plain masked FedAvg.

        scale_i = (1 + s_i)^(-a) / max_j (1 + s_j)^(-a)

    s_i is the number of server versions that advanced between client i's
    dispatch and its arrival (0 = trained on current params); `a` is the
    polynomial discount exponent (FedBuff's s^(-a) family, shifted so s=0
    is well-defined). The max-normalization gives two exact identities the
    async tests pin bitwise:

      * all-fresh buffer (s == 0):    (1+0)^(-a) == 1.0 and x/1.0 == x, so
        every scale is exactly 1.0 — async == sync aggregation;
      * uniformly-stale buffer:       x/x == 1.0 exactly in IEEE754, so a
        buffer where everyone is equally late is NOT down-weighted into a
        vanishing update — relative freshness is what matters.
    """
    s = jnp.asarray(staleness, jnp.float32)
    a = jnp.asarray(exponent, jnp.float32)
    raw = (1.0 + s) ** (-a)
    return raw / jnp.max(raw)


@jax.jit
def aggregate_buffered(global_params, stacked_deltas, weights,
                       mask_bank, mask_idx, staleness, exponent):
    """`aggregate_stacked` for an async arrival buffer (fl/async_rounds.py):
    identical masked-FedAvg pipeline (`partial_sums` -> `combine_partials`),
    with each arrival's sample-count weight scaled by `staleness_scale`
    before BOTH the numerator and the per-mask denominator — a stale
    straggler's coordinates are discounted consistently, so coordinates
    only it trained still average to its (discounted) delta rather than
    shrinking toward zero. With zero staleness everywhere the scaled
    weights equal `weights` bitwise and this is `aggregate_stacked`."""
    w = weights.astype(jnp.float32) * staleness_scale(staleness, exponent)
    k = jax.tree.leaves(mask_bank)[0].shape[0]
    num, w_per_mask = partial_sums(stacked_deltas, w, mask_idx, k)
    return combine_partials(global_params, num, w_per_mask, mask_bank)


def aggregate(global_params, updates: Sequence[ClientUpdate]):
    """Participation-weighted FedAvg."""
    num = jax.tree.map(jnp.zeros_like, global_params)
    den = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                       global_params)
    for u in updates:
        w = float(u.n_samples)
        if u.mask is None:
            num = jax.tree.map(lambda a, d: a + w * d.astype(a.dtype),
                               num, u.delta)
            den = jax.tree.map(lambda a: a + w, den)
        else:
            num = jax.tree.map(
                lambda a, d, m: a + (w * m * d).astype(a.dtype),
                num, u.delta, u.mask)
            den = jax.tree.map(lambda a, m: a + w * m, den, u.mask)
    return jax.tree.map(
        lambda p, n, d: p + jnp.where(d > 0, n / jnp.maximum(d, 1e-12),
                                      0.0).astype(p.dtype),
        global_params, num, den)
