"""RoundBackend protocol: one round-execution contract, three engines.

core/fluid.FluidServer used to special-case "engine or per-client loop"
inline. The population layer needs a third execution mode (sharded fleet)
and per-round backends (every cohort is a fresh client list sampled from
the ClientStore), so the execution strategies are now first-class objects
behind one small protocol:

    backend.clients                       -> the cohort (ordered)
    backend.run_round(params, keep_maps, rates) -> result with
        .sim_times               {cid: emulated seconds}
        .aggregate(params)       -> new global params (masked FedAvg)
        .non_straggler_stats(prev) -> per-client invariant-neuron stats
        .updates()               -> sequential-style ClientUpdates

SequentialBackend is the numerical reference (one jit call per client,
physically extracted sub-models); FleetBackend runs the whole cohort as
one compiled program (fl/fleet.py); ShardedFleetBackend runs that same
program under shard_map over a mesh's data axis (fl/shard_fleet.py). All
three agree up to float summation order (tests/test_population.py,
tests/test_fleet.py). AsyncBufferedBackend (fl/async_rounds.py) drops the
barrier entirely: `run_round` dispatches the cohort and then drains the
first K *arrivals* off the EventLoop below — round membership becomes
data-dependent, and the result carries staleness per arrival.

This module also owns the virtual clock those arrivals ride on: EventLoop
is a deterministic (time, push-order) heap, so a zero-latency-spread run
resolves ties in dispatch order and the whole async schedule reproduces
from the seeds alone.
"""
from __future__ import annotations

import heapq

from dataclasses import dataclass
from typing import Dict, List, Optional, Protocol, Sequence, Tuple

import jax
import numpy as np

from repro.core import invariant as inv
from repro.core import submodel as sub
from repro.core.aggregate import ClientUpdate, aggregate
from repro.fl.fleet import FleetEngine
from repro.fl.shard_fleet import ShardedFleetEngine

BACKEND_NAMES = ("sequential", "fleet", "sharded_fleet", "async")


class EventLoop:
    """Virtual-clock event queue for emulated asynchrony.

    `push(t, payload)` schedules; `pop()` returns the earliest event and
    advances `now` monotonically (a pop never rewinds the clock, even if
    an event was scheduled in the past relative to a later dispatch).
    Ties on `t` break by push order — with zero latency spread the async
    backend therefore drains arrivals in exactly the order it dispatched
    them, which the fleet==async equivalence test pins."""

    def __init__(self):
        self._heap: List[Tuple[float, int, object]] = []
        self._seq = 0
        self.now = 0.0

    def push(self, t: float, payload) -> None:
        heapq.heappush(self._heap, (float(t), self._seq, payload))
        self._seq += 1

    def pop(self):
        t, _, payload = heapq.heappop(self._heap)
        self.now = max(self.now, t)
        return t, payload

    def __len__(self) -> int:
        return len(self._heap)


class RoundResult(Protocol):
    sim_times: Dict[int, float]

    def aggregate(self, global_params): ...
    def non_straggler_stats(self, prev_params) -> List[dict]: ...
    def updates(self) -> List[ClientUpdate]: ...


class RoundBackend(Protocol):
    name: str
    clients: Sequence

    def run_round(self, params, keep_maps: Dict[int, dict],
                  rates: Dict[int, float]) -> RoundResult: ...


# ---------------------------------------------------------------------------
# Sequential reference

@dataclass
class SequentialResult:
    """Per-client ClientUpdates presented through the RoundResult contract."""
    _updates: List[ClientUpdate]
    unit_specs: list

    @property
    def sim_times(self) -> Dict[int, float]:
        return {u.client_id: u.sim_time for u in self._updates}

    def aggregate(self, global_params):
        return aggregate(global_params, self._updates)

    def non_straggler_stats(self, prev_params) -> List[dict]:
        return [inv.neuron_stats(prev_params,
                                 jax.tree.map(lambda p, d: p + d,
                                              prev_params, u.delta),
                                 self.unit_specs)
                for u in self._updates if u.mask is None]

    def updates(self) -> List[ClientUpdate]:
        return list(self._updates)


class SequentialBackend:
    """One jit call per client; stragglers train physically extracted
    sub-models (core/submodel.extract) and their deltas are re-embedded in
    full coordinates — the paper-literal reference path."""
    name = "sequential"

    def __init__(self, clients: Sequence, unit_specs):
        self.clients = list(clients)
        self.unit_specs = unit_specs

    def run_round(self, params, keep_maps, rates) -> SequentialResult:
        updates: List[ClientUpdate] = []
        for c in self.clients:
            if c.id in keep_maps:
                keep, r = keep_maps[c.id], rates[c.id]
                sub_params = sub.extract(params, self.unit_specs, keep)
                u = c.train(sub_params, keep_map=keep, rate=r)
                full_delta, mask = sub.embed_delta(
                    u.delta, params, self.unit_specs, keep)
                u = ClientUpdate(full_delta, u.n_samples, mask,
                                 u.sim_time, c.id)
            else:
                u = c.train(params)
            updates.append(u)
        return SequentialResult(updates, self.unit_specs)


# ---------------------------------------------------------------------------
# Fleet backends: CohortResult already satisfies RoundResult

class FleetBackend:
    """The whole cohort as one masked-SGD program, clients mapped one at a
    time."""
    name = "fleet"

    def __init__(self, engine: FleetEngine):
        self.engine = engine

    @property
    def clients(self):
        return self.engine.clients

    def run_round(self, params, keep_maps, rates):
        return self.engine.run_cohort(params, keep_maps, rates)


class ShardedFleetBackend(FleetBackend):
    """The fleet program under shard_map with hierarchical aggregation."""
    name = "sharded_fleet"

    def __init__(self, engine: ShardedFleetEngine):
        super().__init__(engine)


def make_backend(name: str, model_cls, clients, unit_specs,
                 use_kernels: bool = False, mesh=None,
                 n_shards: Optional[int] = None,
                 async_cfg=None) -> RoundBackend:
    """Construct a RoundBackend for one cohort.

    sharded_fleet resolves its shard count as: explicit n_shards if given,
    else the largest device count that divides the cohort
    (gcd(|cohort|, data-axis devices)) — degenerating to an unsharded
    1-device mesh rather than erroring on awkward cohort sizes.

    "async" constructs an AsyncBufferedBackend with `clients` as its first
    dispatch group. Unlike the synchronous backends it is STATEFUL across
    rounds (virtual clock, in-flight arrival heap, server version) — reuse
    the same instance and re-point `set_dispatch(...)` per round, as
    fl/async_rounds.AsyncPopulationSim does; building a fresh one per
    round silently discards every in-flight client."""
    if name == "async":
        from repro.fl.async_rounds import AsyncBufferedBackend, AsyncConfig
        backend = AsyncBufferedBackend(model_cls, unit_specs,
                                       async_cfg or AsyncConfig(),
                                       use_kernels=use_kernels)
        backend.set_dispatch(clients)
        return backend
    if name == "sequential":
        return SequentialBackend(clients, unit_specs)
    if name == "fleet":
        return FleetBackend(FleetEngine(model_cls, clients, unit_specs,
                                        use_kernels=use_kernels))
    if name == "sharded_fleet":
        if n_shards is None:
            if mesh is not None:
                n_shards = mesh.shape["data"]
            else:
                from repro.launch.mesh import make_host_mesh
                n_shards = int(np.gcd(len(clients), len(jax.devices())))
                mesh = make_host_mesh(data=n_shards)
        return ShardedFleetBackend(
            ShardedFleetEngine(model_cls, clients, unit_specs, mesh=mesh,
                               n_shards=n_shards, use_kernels=use_kernels))
    raise ValueError(f"backend must be one of {BACKEND_NAMES}, got {name!r}")
