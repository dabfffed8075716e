"""The comparison that decides `correct`.

The set-up rounds go through the window's own call on the same object the
window then drives, and the reference (bench/reference/fluid_ref.py)
follows them from the same initial weights. Numbers compared, each against
its limit in bench/limits/<cell>.json:

  loss_gap       each round's loss, |L_prog - L_ref| / L_ref at worst: the
                 mean of the family's `example_loss` (for the CNN family,
                 cross-entropy) of the global params after the round on a
                 seeded sample of the cell's training rows, evaluated in
                 float32 at "highest";
  update_gap     the first round's update of the global params (FedAvg's
                 gradient as the server applies it), by the worst leaf:
                 | ||d_prog|| - ||d_ref|| | / max(||d_ref||, median leaf's);
  change_gap     the same for the change of the params over all the set-up
                 rounds, over the leaves the reference's first update moves
                 (norm at least a thousandth of the median leaf's);
  plan_mismatch  clients on which the two disagree, summed over rounds:
                 who trained a sub-model, and the straggler plan each
                 calibration made (exact: limit 0).

The units each invariant keep map drops are not compared: units near the
policy's tie flip between the two at rounding (1 to 8 of the CNN's 200 on
the chip, as often under the control), so no limit separates them.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

MOVED = 1e-3          # a leaf moves if its norm is >= MOVED x median leaf's


def _leaf_norms(tree) -> List[float]:
    return [float(np.linalg.norm(np.asarray(a, np.float64)))
            for a in jax.tree.leaves(tree)]


def _gap(prog, ref, theta0, leaves=None) -> float:
    dp = jax.tree.map(lambda a, b: np.asarray(a, np.float64) - b, prog, theta0)
    dr = jax.tree.map(lambda a, b: np.asarray(a, np.float64) - b, ref, theta0)
    n_p, n_r = _leaf_norms(dp), _leaf_norms(dr)
    floor = float(np.median(n_r))
    idx = range(len(n_r)) if leaves is None else leaves
    return max(abs(n_p[i] - n_r[i]) / max(n_r[i], floor, 1e-30) for i in idx)


def _moved_leaves(first_ref, theta0) -> List[int]:
    d = jax.tree.map(lambda a, b: np.asarray(a, np.float64) - b,
                     first_ref, theta0)
    n = _leaf_norms(d)
    med = float(np.median(n))
    return [i for i, v in enumerate(n) if v >= MOVED * med]


_LOSS = {}


def _loss_fn(family, config):
    key = (family.__name__, config["name"])
    if key in _LOSS:
        return _LOSS[key]

    @jax.jit
    def loss(params, x, y):
        return jnp.sum(family.example_loss(config, params, x, y,
                                           jax.lax.Precision.HIGHEST))
    _LOSS[key] = loss
    return loss


def eval_rows(n: int, seed: int, size: int = 1024) -> np.ndarray:
    return np.sort(np.random.RandomState(seed).choice(n, min(size, n),
                                                      replace=False))


def mean_loss(family, config, params, x, y, block: int = 256) -> float:
    loss = _loss_fn(family, config)
    tot = 0.0
    for i in range(0, len(y), block):
        tot += float(loss(params, jnp.asarray(x[i:i + block]),
                          jnp.asarray(y[i:i + block])))
    return tot / len(y)


def compare(family, config, theta0, prog, ref, x_eval, y_eval
            ) -> Dict[str, float]:
    """prog, ref: RoundRecords of the program's set-up rounds and of the
    reference's, the same number from the same theta0 (host arrays)."""
    if len(prog) != len(ref):
        raise ValueError("program and reference ran different round counts")
    theta0 = jax.tree.map(lambda a: np.asarray(a, np.float64), theta0)
    loss_gap = 0.0
    for p, r in zip(prog, ref):
        lp = mean_loss(family, config, p.params, x_eval, y_eval)
        lr = mean_loss(family, config, r.params, x_eval, y_eval)
        loss_gap = max(loss_gap, abs(lp - lr) / lr if np.isfinite(lp)
                       else float("inf"))
    plan = 0
    for p, r in zip(prog, ref):
        plan += len(set(p.cohort) ^ set(r.cohort))
        plan += len(set(p.sub_model_clients) ^ set(r.sub_model_clients))
        plan += len(set(p.stragglers) ^ set(r.stragglers))
    finite = all(np.all(np.isfinite(a)) for a in jax.tree.leaves(
        prog[-1].params))
    return {
        "loss_gap": loss_gap,
        "update_gap": _gap(prog[0].params, ref[0].params, theta0),
        "change_gap": (_gap(prog[-1].params, ref[-1].params, theta0,
                            _moved_leaves(ref[0].params, theta0))
                       if finite else float("inf")),
        "plan_mismatch": float(plan),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, [(name, value, limit)]) — every number within its limit."""
    rows = [(k, numbers[k], float(limits[k])) for k in numbers]
    ok = all(np.isfinite(v) and v <= lim for _, v, lim in rows)
    return bool(ok), rows
