"""Inputs the benchmark makes from the seed for a fixed-cohort traffic mix.

Every client holds exactly `samples_per_client` rows, so every seed gives
the same shapes (and the same compiled programs) in another order of
values. What a row is belongs to the configuration's model family
(`bench/reference/<family>.py`, its `rows`): for the CNN family, an image
drawn as a class prototype plus one of the client's writers' style plus
noise, with one class label. The speeds are drawn here, after the rows,
from the same generator.

Per-epoch speeds: a fast cluster at base x (1 + 0.05 N(0,1)) and the
planted stragglers at base x slow_factor (the paper's Fig. 4a phones are
10-32% slower).
"""
from __future__ import annotations

import importlib

import numpy as np

BASE_SPEED = 10.0


def family_of(config: dict):
    """The model family module the configuration names."""
    return importlib.import_module(f"bench.reference.{config['family']}")


def straggler_list(traffic: dict):
    """The planted stragglers: a list, or {"first": n} for ids 0..n-1."""
    s = traffic["straggler_ids"]
    return list(range(int(s["first"]))) if isinstance(s, dict) else list(s)


def cohort_data(config: dict, traffic: dict, seed: int):
    """(x, y, parts, speeds): the family's rows and labels, each client's
    row indices, and its seconds per epoch."""
    rng = np.random.default_rng(seed)
    c = int(traffic["n_clients"])
    s = int(traffic["samples_per_client"])
    x, y = family_of(config).rows(config, rng, c, s,
                                  int(traffic["writers_per_client"]))
    parts = [np.arange(i * s, (i + 1) * s) for i in range(c)]
    speeds = BASE_SPEED * (1.0 + 0.05 * rng.standard_normal(c))
    speeds[straggler_list(traffic)] = BASE_SPEED * float(
        traffic["slow_factor"])
    return x, y, parts, speeds
