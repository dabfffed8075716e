"""Invariant-neuron statistics and drop-threshold calibration (paper §4, §5).

A neuron's *update statistic* for one client is the maximum relative weight
change over all weights that produce it:

    g_i = max_w |w(t) - w(t-1)| / (|w(t-1)| + eps)

(the paper's "minimum g such that g >= (w(t)-w(t-1))/w(t-1)" — i.e. the
tightest bound covering every weight of the neuron).

A neuron is *invariant* at threshold th when g_i <= th for the **majority of
non-straggler clients** (stragglers train sub-models, so the server never
uses their updates for this). The initial threshold is the client-average of
the per-client minimum neuron stat; it is then incremented geometrically
until at least the target number of neurons is invariant (Algorithm 1,
lines 9 / 22).

The statistics are computed on the device; the votes and the threshold
search run on the host, in NumPy, over one (N, size) array per unit group.
"""
from __future__ import annotations

from typing import Dict, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core import spans

EPS = 1e-8
TH_GROWTH = 1.25


def _get(tree, path: str):
    node = tree
    for part in path.split("/"):
        node = node[part]
    return node


def _per_unit(arr, axis, tile, size):
    """Group an array's producer weights by unit: -> (size, -1).

    Mirrors submodel.expand_indices' grammar: tile>0 is tile-major
    (unit index fastest along the axis), tile<0 is unit-major (each unit
    owns |tile| contiguous slots — the attention-head layout)."""
    a = jnp.moveaxis(arr, axis, 0)
    if tile < 0:
        return a.reshape(size, -1)
    return a.reshape(tile, size, -1).transpose(1, 0, 2).reshape(size, -1)


def neuron_stats_for_group(prev_tree, new_tree, group,
                           kind: str = "norm") -> jnp.ndarray:
    """Per-neuron relative update statistic over the group's producers.

    kind="norm" (default): ||Δw|| / (||w(t-1)|| + eps) per neuron — one
    relative "percent difference g of the neuron" (paper §5). kind="max":
    per-weight max relative delta (dominated by near-zero weights; kept for
    ablation). Returns (size,) float32."""
    size = group["size"]
    if kind == "max":
        stats = jnp.zeros((size,), jnp.float32)
        for path, axis, tile in group["out"]:
            w0 = _get(prev_tree, path).astype(jnp.float32)
            w1 = _get(new_tree, path).astype(jnp.float32)
            rel = jnp.abs(w1 - w0) / (jnp.abs(w0) + EPS)
            rel = _per_unit(rel, axis, tile, size)
            stats = jnp.maximum(stats, rel.max(axis=1))
        return stats
    num = jnp.zeros((size,), jnp.float32)
    den = jnp.zeros((size,), jnp.float32)
    for path, axis, tile in group["out"]:
        w0 = _get(prev_tree, path).astype(jnp.float32)
        w1 = _get(new_tree, path).astype(jnp.float32)
        d2 = _per_unit(jnp.square(w1 - w0), axis, tile, size)
        w2 = _per_unit(jnp.square(w0), axis, tile, size)
        num = num + d2.sum(axis=1)
        den = den + w2.sum(axis=1)
    return jnp.sqrt(num) / (jnp.sqrt(den) + EPS)


def neuron_stats(prev_tree, new_tree, unit_specs,
                 kind: str = "norm") -> Dict[str, jnp.ndarray]:
    return {g["name"]: neuron_stats_for_group(prev_tree, new_tree, g, kind)
            for g in unit_specs}


def _stack(per_client_stats) -> Dict[str, np.ndarray]:
    """Per group: the clients' stats as one (N, size) float32 host array.
    Rows may be NumPy arrays or jax arrays (the sequential backend's)."""
    return {g: np.stack([np.asarray(cs[g]) for cs in per_client_stats])
            .astype(np.float32, copy=False)
            for g in per_client_stats[0]}


def _votes(stacked: Dict[str, np.ndarray], th: float) -> Dict[str, np.ndarray]:
    # th compares as float32, as a weakly typed Python float meets a
    # float32 array, so the votes do not move with th's float64 digits
    th32 = np.float32(th)
    return {g: (a <= th32).sum(axis=0, dtype=np.int32)
            for g, a in stacked.items()}


def _majority(stacked: Dict[str, np.ndarray],
              th: float) -> Dict[str, np.ndarray]:
    n = len(next(iter(stacked.values())))
    return {g: c > n / 2 for g, c in _votes(stacked, th).items()}


def _n_invariant(stacked: Dict[str, np.ndarray], th: float) -> int:
    return int(sum(m.sum() for m in _majority(stacked, th).values()))


def initial_threshold(per_client_stats: Sequence[Dict[str, np.ndarray]]):
    """Average over clients of the min percent-update over all neurons."""
    mins = np.min([a.min(axis=1) for a in _stack(per_client_stats).values()],
                  axis=0)
    return float(mins.mean(dtype=np.float64).astype(np.float32))


def invariant_counts(per_client_stats: Sequence[Dict[str, np.ndarray]],
                     th: float) -> Dict[str, np.ndarray]:
    """Per group: #clients for which each neuron is below th."""
    return _votes(_stack(per_client_stats), th)


def mean_stats(per_client_stats) -> Dict[str, np.ndarray]:
    return {g: a.mean(axis=0, dtype=np.float64).astype(np.float32)
            for g, a in _stack(per_client_stats).items()}


def invariant_mask(per_client_stats, th: float) -> Dict[str, np.ndarray]:
    """Neurons invariant for the strict majority of clients."""
    return _majority(_stack(per_client_stats), th)


def count_invariant(per_client_stats, th: float) -> int:
    return _n_invariant(_stack(per_client_stats), th)


def calibrate_threshold(per_client_stats, n_drop_target: int, th0: float,
                        max_iters: int = 200) -> float:
    """Increment th until #invariant >= n_drop_target (Algorithm 1 l.22)."""
    stacked = _stack(per_client_stats)
    th = max(float(th0), EPS)
    for _ in range(max_iters):
        spans.count("fluid.threshold_iters")
        if _n_invariant(stacked, th) >= n_drop_target:
            return th
        th *= TH_GROWTH
    return th
