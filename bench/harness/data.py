"""Inputs the benchmark makes from the seed for a fixed-cohort traffic mix.

Every client holds exactly `samples_per_client` rows, so every seed gives
the same shapes (and the same compiled programs) in another order of
values. Rows follow the shape of FEMNIST/CIFAR-style writer data: a class
prototype, plus the style of one of the client's own `writers_per_client`
writers, plus noise; labels are uniform, so the skew between clients is by
writer, as in LEAF's FEMNIST split.

Per-epoch speeds: a fast cluster at base x (1 + 0.05 N(0,1)) and the
planted stragglers at base x slow_factor (the paper's Fig. 4a phones are
10-32% slower).
"""
from __future__ import annotations

import numpy as np

BASE_SPEED = 10.0


def straggler_list(traffic: dict):
    """The planted stragglers: a list, or {"first": n} for ids 0..n-1."""
    s = traffic["straggler_ids"]
    return list(range(int(s["first"]))) if isinstance(s, dict) else list(s)


def cohort_data(config: dict, traffic: dict, seed: int):
    """(x, y, parts, speeds): float32 rows, int32 labels, each client's row
    indices, and its seconds per epoch."""
    rng = np.random.default_rng(seed)
    c = int(traffic["n_clients"])
    s = int(traffic["samples_per_client"])
    wpc = int(traffic["writers_per_client"])
    shape = tuple(config["input_shape"])
    k = int(config["num_classes"])
    protos = rng.standard_normal((k, *shape), dtype=np.float32)
    styles = 0.6 * rng.standard_normal((c * wpc, *shape), dtype=np.float32)
    y = rng.integers(0, k, size=c * s).astype(np.int32)
    writer = (np.repeat(np.arange(c), s) * wpc
              + np.tile(np.arange(s) % wpc, c))
    x = protos[y]
    x += 1.2 * rng.standard_normal(x.shape, dtype=np.float32)
    x += styles[writer]
    parts = [np.arange(i * s, (i + 1) * s) for i in range(c)]
    speeds = BASE_SPEED * (1.0 + 0.05 * rng.standard_normal(c))
    speeds[straggler_list(traffic)] = BASE_SPEED * float(
        traffic["slow_factor"])
    return x, y, parts, speeds
