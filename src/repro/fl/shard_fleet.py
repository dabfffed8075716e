"""Sharded cohort executor: the fleet program under shard_map.

fl/fleet.py runs one masked-SGD program for a whole cohort on one device.
This module scales that same program over the ``data`` axis of a
launch/mesh.py mesh: the cohort is split into `n_shards` logical shards of
equal size, each device runs its shards through the *identical* inner
cohort program, reduces each shard to the masked-FedAvg sufficient
statistics (core/aggregate.partial_sums), and a single `jax.lax.psum`
finishes the hierarchical aggregation. Params and the MaskBank are
replicated (in_specs P()); only per-client tensors are sharded, the
clients' resident samples among them (uploaded once, already split over
the devices).

Determinism contract: the logical shard count S is part of the *numerical*
program, independent of the device count D (each device owns S/D shards).
Two implementation choices make per-shard arithmetic reproduce bit-for-bit
across device counts, and both were found empirically (tests/
test_population.py locks them in):

  * The local shards are a *Python-unrolled* loop, not jax.lax.map — the
    loop body compiles in a different fusion context for length-2 vs
    length-1 scans, which perturbs the per-shard deltas by 1 ULP.
  * Each shard's partials pass through jax.lax.optimization_barrier AND
    are materialized as a program output (`shard_partials` on the result).
    The barrier keeps the cross-shard reduction out of the per-shard
    tensordots; the output forces each shard's partials into its own
    buffer, which stops XLA from horizontally merging the co-resident
    tensordot instances on low device counts (the merge retiles the
    contraction and moves `num` by 1 ULP — observed with the barrier
    alone). The materialized partials are S param-trees — noise next to
    the (C, ...) deltas — and double as the inspection point for the
    hierarchical-aggregation tests.

The cross-shard reduction is then a fixed left-to-right add chain locally
plus a psum across devices — a two-term psum is bitwise equal to the plain
add (verified directly) — so runs whose reduction trees coincide are
bitwise identical. In particular S=2 on D=1 (local a0+a1) and on D=2
(two-term psum) produce bit-identical aggregated params. For general
(S, D) the association differs and results agree only up to float
summation order — the same caveat as fleet vs sequential. The bitwise
identity is pinned on CPU host devices. On a TPU the cross-chip
all-reduce may add the partials in another order than the local chain:
S=4 on four v5e chips against S=4 on one chip gives the same cohorts and
straggler plans, and params that differ by a few ULP (chip_smoke.py
--chips 4 holds them to 1e-5).

Everything else (mask bank construction, sim-time draws, CohortResult
views) is inherited from FleetEngine; only `_execute` and the placement of
the resident samples change, plus an `aggregate` that applies the
already-reduced partials instead of recomputing them from gathered
deltas.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.aggregate import combine_partials, partial_sums
from repro.fl.client import FleetClient
from repro.fl.fleet import CohortResult, FleetEngine, _cohort_fn
from repro.kernels import ops as kops
from repro.launch.mesh import make_host_mesh

_SHARDED_CACHE: Dict[tuple, callable] = {}

_combine = jax.jit(combine_partials)


def _tree_add(t1, t2):
    return jax.tree.map(jnp.add, t1, t2)


def _sharded_cohort_fn(model_cls, mesh, n_shards: int, use_kernels: bool):
    """One compiled program per (model, mesh, shard count): masked local SGD
    for all shards + hierarchical masked-FedAvg partials.

    Signature: run(params, bank, idx, client_x, order, ys, sw, lrs, w,
    n_steps) where per-client operands carry leading (S, Cs) dims. Returns
    (deltas (S, Cs, ...), shard_partials ((S, ...) num tree + (S, K)
    weights), num tree (param shapes), w_per_mask (K,)) with num/
    w_per_mask already fully reduced (replicated on every device).
    """
    key = (model_cls.__name__, mesh, n_shards, use_kernels,
           kops._default_interpret())
    if key not in _SHARDED_CACHE:
        inner = _cohort_fn(model_cls, use_kernels)
        d_dev = mesh.shape["data"]
        local = n_shards // d_dev      # shards per device

        @functools.partial(jax.jit, static_argnames=("n_steps",))
        def run(params, bank, idx, client_x, order, ys, sw, lrs, w, n_steps):
            k = jax.tree.leaves(bank)[0].shape[0]

            def body(p, b, mi, cx, o, y, v, lr, wv):
                # block-local leaves: (local, Cs, ...). The shard loop is
                # Python-unrolled on purpose (bounded by S/D) and each
                # shard's partials are barriered + materialized — see the
                # determinism contract in the module docstring.
                ds, parts = [], []
                for s in range(local):
                    d = inner(p, b, mi[s], cx[s], o[s], y[s], v[s], lr[s],
                              n_steps)
                    parts.append(jax.lax.optimization_barrier(
                        partial_sums(d, wv[s], mi[s], k)))
                    ds.append(d)
                d = jax.tree.map(lambda *a: jnp.stack(a), *ds)
                pr = jax.tree.map(lambda *a: jnp.stack(a), *parts)
                # fixed left-to-right chain: explicit program structure,
                # not a rewritable reduction
                num, wpm = functools.reduce(_tree_add, parts)
                num = jax.tree.map(lambda a: jax.lax.psum(a, "data"), num)
                wpm = jax.lax.psum(wpm, "data")
                return d, pr, num, wpm

            # check_vma=False: psum makes num/wpm replicated by construction
            f = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(), P("data"), P("data"), P("data"),
                          P("data"), P("data"), P("data"), P("data")),
                out_specs=(P("data"), P("data"), P(), P()),
                check_vma=False)
            return f(params, bank, idx, client_x, order, ys, sw, lrs, w)
        _SHARDED_CACHE[key] = run
    return _SHARDED_CACHE[key]


@dataclass
class ShardedCohortResult(CohortResult):
    """CohortResult + the hierarchically-reduced aggregation partials."""
    num: Optional[dict] = None            # tree of param-shaped sums
    w_per_mask: Optional[jnp.ndarray] = None   # (K,)
    shard_partials: Optional[tuple] = None     # ((S, ...) num, (S, K) w)

    def aggregate(self, global_params):
        """Apply the psum-reduced partials (core/aggregate.combine_partials)
        — no second pass over the (C, ...) deltas."""
        return _combine(global_params, self.num, self.w_per_mask,
                        self.mask_bank)


class ShardedFleetEngine(FleetEngine):
    """FleetEngine whose cohort program runs under shard_map.

    n_shards: logical shard count S (defaults to the mesh's data-axis
    size). Requirements, loudly enforced: S divides the cohort size and the
    data-axis device count divides S. The (S, Cs) layout is row-major in
    client order, so shard s holds clients [s*Cs, (s+1)*Cs).
    """

    def __init__(self, model_cls, clients: Sequence[FleetClient], unit_specs,
                 mesh=None, n_shards: Optional[int] = None,
                 use_kernels: bool = False):
        super().__init__(model_cls, clients, unit_specs,
                         use_kernels=use_kernels)
        if mesh is None:
            mesh = make_host_mesh(data=len(jax.devices()))
        if "data" not in mesh.axis_names:
            raise ValueError(f"mesh needs a 'data' axis, got "
                             f"{mesh.axis_names}")
        d_dev = mesh.shape["data"]
        n_shards = d_dev if n_shards is None else int(n_shards)
        if n_shards % d_dev:
            raise ValueError(
                f"n_shards={n_shards} must be a multiple of the mesh's "
                f"data-axis size {d_dev} (each device owns S/D shards)")
        c = len(self.clients)
        if c % n_shards:
            raise ValueError(
                f"cohort size {c} must divide evenly into n_shards="
                f"{n_shards} (equal shards keep one compiled shape)")
        self.mesh = mesh
        self.n_shards = n_shards
        self._sharded = _sharded_cohort_fn(model_cls, mesh, n_shards,
                                           self.use_kernels)

    def _split(self, a):
        """(C, ...) -> (S, Cs, ...): shard s holds clients [s*Cs, (s+1)*Cs)."""
        return a.reshape((self.n_shards, len(self.clients) // self.n_shards)
                         + a.shape[1:])

    def _put_client_data(self, x):
        return jax.device_put(self._split(x),
                              NamedSharding(self.mesh, P("data")))

    def _execute(self, params, bank, idx, order, ys, sw, lrs, weights):
        s, cs = self.n_shards, len(self.clients) // self.n_shards
        resh = self._split
        d, pr, num, wpm = self._sharded(params, bank, resh(idx),
                                        self._client_data(), resh(order),
                                        resh(ys), resh(sw), resh(lrs),
                                        resh(weights), self.steps)
        deltas = jax.tree.map(
            lambda a: a.reshape((s * cs,) + a.shape[2:]), d)
        return deltas, (num, wpm, pr)

    def _wrap_result(self, extra, **kw) -> ShardedCohortResult:
        num, wpm, pr = extra
        return ShardedCohortResult(num=num, w_per_mask=wpm,
                                   shard_partials=pr, **kw)
