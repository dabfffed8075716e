"""Asynchronous buffered rounds: masked FedAvg without the cohort barrier.

The synchronous backends (fl/rounds.py) hold a barrier over the cohort:
even with invariant dropout shrinking straggler sub-models, one slow or
disconnected client bounds wall-clock between calibrations. This backend
drops the barrier, FedBuff-style, while keeping every FLuID invariant-
dropout mechanism intact:

  * clients are DISPATCHED with the current params and the keep-masks the
    store assigned them, in fixed-size groups of `buffer_k` (the last group
    capacity-padded via FleetEngine's partial-cohort `members=` — program
    shapes never depend on how many clients happened to be free);
  * each dispatched client's masked delta is computed eagerly (it depends
    only on the dispatch-time params) and its ARRIVAL is scheduled on a
    virtual clock (fl/rounds.EventLoop) at now + latency, where latency is
    the client speed model's draw passed through the arrival process
    (core/straggler.ArrivalModel: heavy tails, mid-round dropouts that
    reconnect and resume);
  * one "round" = drain the first `buffer_k` arrivals off the clock and
    aggregate them with staleness-weighted masked FedAvg
    (core/aggregate.aggregate_buffered — the same partial_sums /
    combine_partials pipeline as the fleet, with each arrival's weight
    discounted by (1+s)^(-a), max-normalized). A straggler that misses the
    buffer is NOT dropped: its delta stays on the heap and lands in a
    later buffer with staleness = #server versions it missed.

Fixed-shape discipline (DESIGN.md §13): dispatch groups are always exactly
buffer_k clients, the drained buffer is always exactly buffer_k arrivals,
and the rebuilt buffer mask bank deduplicates to the same row count the
dispatch banks had — so at steady state (constant calibration output) the
dispatch program, the stats program, and `aggregate_buffered` each compile
once, whatever arrival order the clock produces. Verified by the
`single-trace-async` contract in repro/analysis/contracts.py.

Determinism ladder (tests/test_async.py): with a zero-spread ArrivalModel
and zero client tail_sigma, arrival order degenerates to dispatch order
(EventLoop breaks time ties by push order), and an async run with
buffer_k = concurrency = cohort_size reproduces the synchronous fleet
run BITWISE — same cohorts, same deltas, same aggregated params, same
calibration plans — because every identity in the chain is exact:
lognormal(0) multiplier == 1.0, staleness 0 ⇒ scale == 1.0, w * 1.0 == w.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregate import ClientUpdate, aggregate_buffered
from repro.core.straggler import ArrivalModel
from repro.fl.fleet import CohortResult, FleetEngine
from repro.fl.population import PopulationSim
from repro.fl.rounds import EventLoop


@dataclass
class AsyncConfig:
    """Async buffered-round policy.

    buffer_k: arrivals aggregated per server step (and the dispatch-group
    capacity). concurrency: target number of in-flight clients the
    population driver maintains (FedBuff's M); must be >= buffer_k so a
    buffer can always fill. staleness_exponent: the `a` of the (1+s)^(-a)
    discount (0 = ignore staleness). flash_crowds: (server_step, extra)
    pairs — at that step the driver dispatches `extra` clients beyond the
    concurrency target, emulating a reconnect surge; the surplus drains
    back to `concurrency` over the following buffers."""
    buffer_k: int = 8
    concurrency: int = 64
    staleness_exponent: float = 0.5
    arrival: ArrivalModel = field(default_factory=ArrivalModel)
    flash_crowds: Sequence[Tuple[int, int]] = ()

    def __post_init__(self):
        if self.buffer_k < 1:
            raise ValueError(f"buffer_k must be >= 1, got {self.buffer_k}")
        if self.concurrency < self.buffer_k:
            raise ValueError(
                f"concurrency ({self.concurrency}) must be >= buffer_k "
                f"({self.buffer_k}): the buffer could never fill")
        if self.staleness_exponent < 0.0:
            raise ValueError(f"staleness_exponent must be >= 0, "
                             f"got {self.staleness_exponent}")


@dataclass
class _InFlight:
    """One dispatched client riding the event loop: which slot of which
    dispatch-group result it owns, and what the server knew at dispatch."""
    cid: int
    version: int                 # server version at dispatch
    slot: int                    # row in the dispatch group's stacked result
    result: CohortResult         # the (buffer_k,)-shaped dispatch outputs
    latency: float               # end-to-end arrival latency (sim seconds)
    rate: float                  # sub-model size trained
    stats: Optional[dict]        # dispatch-time invariant stats (non-strag)
    drops: int                   # mid-round dropouts survived


@dataclass
class AsyncRoundResult:
    """RoundResult over one drained buffer (fl/rounds.py protocol, plus the
    async-only fields core/fluid.FluidServer reads via getattr: clock,
    staleness, rates_trained, calib_ids)."""
    arrivals: List[_InFlight]    # canonical order: (dispatch version, slot)
    version: int                 # server version aggregating this buffer
    clock: float                 # virtual time when the buffer filled
    exponent: float

    @property
    def sim_times(self) -> Dict[int, float]:
        return {a.cid: a.latency for a in self.arrivals}

    @property
    def rates_trained(self) -> Dict[int, float]:
        """Rate each arrival ACTUALLY trained (assigned at its dispatch) —
        the server must not de-normalize latencies with rates it assigned
        to this step's fresh dispatches."""
        return {a.cid: a.rate for a in self.arrivals}

    @property
    def calib_ids(self) -> List[int]:
        """Who recalibration reasons about: the clients with fresh
        observations, i.e. this buffer's arrivals (sorted, like a cohort)."""
        return sorted(a.cid for a in self.arrivals)

    @property
    def staleness(self) -> np.ndarray:
        return np.asarray([self.version - a.version for a in self.arrivals],
                          np.float32)

    def _buffer_bank(self):
        """Rebuild (bank, idx) over the buffer from the arrivals' dispatch
        banks: all-ones row 0 + one row per distinct straggler mask, in
        first-encounter order over the canonical arrival order. Dedupe key
        is (dispatch result, row): rows of one dispatch bank are distinct
        by construction, and MaskBank already content-deduped within each
        dispatch. Encounter order equals ascending-cid order for a single
        dispatch group, so the rebuilt bank reproduces the dispatch bank
        exactly — the bitwise anchor of the fleet==async equivalence."""
        ones = jax.tree.map(lambda b: b[0], self.arrivals[0].result.mask_bank)
        rows, row_map, idx = [ones], {}, []
        for a in self.arrivals:
            r = int(a.result.mask_idx[a.slot])
            if r == 0:
                idx.append(0)
                continue
            key = (id(a.result), r)
            if key not in row_map:
                row_map[key] = len(rows)
                rows.append(jax.tree.map(lambda b: b[r], a.result.mask_bank))
            idx.append(row_map[key])
        bank = jax.tree.map(lambda *rs: jnp.stack(rs), *rows)
        return bank, jnp.asarray(idx, jnp.int32)

    def aggregate(self, global_params):
        """Staleness-weighted masked FedAvg over the buffer. Deltas arrive
        mask-pre-zeroed from the dispatch programs, so stacking the
        arrivals' rows feeds core/aggregate.aggregate_buffered the exact
        inputs aggregate_stacked would see for a synchronous cohort."""
        deltas = jax.tree.map(
            lambda *rows: jnp.stack(rows),
            *[jax.tree.map(lambda d: d[a.slot], a.result.deltas)
              for a in self.arrivals])
        weights = jnp.stack([a.result.weights[a.slot]
                             for a in self.arrivals])
        bank, idx = self._buffer_bank()
        return aggregate_buffered(global_params, deltas, weights, bank, idx,
                                  self.staleness, self.exponent)

    def non_straggler_stats(self, prev_params) -> List[dict]:
        """Invariant-neuron stats of the buffer's full-model arrivals.
        Computed at DISPATCH time against the dispatch params (the delta's
        own baseline); `prev_params` is ignored — an async server has no
        single "previous params" for a mixed-staleness buffer."""
        del prev_params
        return [a.stats for a in self.arrivals if a.stats is not None]

    def updates(self) -> List[ClientUpdate]:
        out = []
        for a in self.arrivals:
            delta = jax.tree.map(lambda d: d[a.slot], a.result.deltas)
            mask = None
            if a.cid in a.result.straggler_ids:
                row = int(a.result.mask_idx[a.slot])
                mask = jax.tree.map(lambda b: b[row], a.result.mask_bank)
            out.append(ClientUpdate(delta, int(a.result.weights[a.slot]),
                                    mask, a.latency, a.cid))
        return out


class AsyncBufferedBackend:
    """RoundBackend without a barrier: dispatch eagerly, aggregate the
    first buffer_k arrivals, keep the rest in flight.

    STATEFUL across rounds (virtual clock, arrival heap, in-flight set,
    server version) — construct once and re-point `set_dispatch` each
    round. `clients` is only the NEXT dispatch group, not the buffer: the
    aggregated clients are whoever arrives first."""
    name = "async"

    def __init__(self, model_cls, unit_specs, cfg: AsyncConfig,
                 use_kernels: bool = False):
        self.model_cls = model_cls
        self.unit_specs = unit_specs
        self.cfg = cfg
        self.use_kernels = bool(use_kernels)
        self.loop = EventLoop()
        self.version = 0
        self.clients: List = []          # next dispatch group
        self.in_flight_ids: set = set()
        self.last_arrived: List[int] = []
        self.last_result: Optional[AsyncRoundResult] = None
        self.n_dispatched = 0
        self.total_drops = 0

    # ------------------------------------------------------------- wiring
    def set_dispatch(self, clients: Sequence) -> None:
        """Point the backend at the next round's dispatch group (clients
        already in flight are skipped at dispatch time)."""
        self.clients = list(clients)

    # ----------------------------------------------------------- dispatch
    def _dispatch_chunk(self, params, chunk, keep_maps, rates, members):
        """Run one capacity-padded dispatch group NOW and schedule its
        arrivals. The delta depends only on the dispatch params, so it is
        computed eagerly; only its *visibility* to the server is delayed."""
        engine = FleetEngine(self.model_cls, chunk, self.unit_specs,
                             use_kernels=self.use_kernels)
        ids_here = {c.id for c in chunk}
        km = {cid: m for cid, m in keep_maps.items() if cid in ids_here}
        res = engine.run_cohort(params, km, rates, members=members)
        stats = res.non_straggler_stats(params)
        stat_slots = [i for i, cid in enumerate(res.client_ids)
                      if cid not in res.straggler_ids
                      and (members is None or members[i])]
        by_slot = dict(zip(stat_slots, stats))
        for slot, c in enumerate(chunk):
            if members is not None and not members[slot]:
                continue
            lat, drops = self.cfg.arrival.draw(res.sim_times[c.id])
            self.loop.push(
                self.loop.now + lat,
                _InFlight(c.id, self.version, slot, res, lat,
                          rates.get(c.id, 1.0), by_slot.get(slot), drops))
            self.in_flight_ids.add(c.id)
            self.n_dispatched += 1
            self.total_drops += drops

    # -------------------------------------------------------------- round
    def run_round(self, params, keep_maps: Dict[int, dict],
                  rates: Dict[int, float]) -> AsyncRoundResult:
        K = self.cfg.buffer_k
        group = [c for c in self.clients if c.id not in self.in_flight_ids]
        for i in range(0, len(group), K):
            chunk = list(group[i:i + K])
            members = None
            if len(chunk) < K:
                members = np.zeros(K, bool)
                members[:len(chunk)] = True
                # pad with clones under reserved negative ids: replace()
                # re-runs __post_init__, so the pads own fresh RNG streams
                # and the real clients' draws are untouched (not that a
                # pad ever draws — it runs 0 steps and no sim time)
                chunk += [dataclasses.replace(chunk[0], id=-(j + 1))
                          for j in range(K - len(chunk))]
            self._dispatch_chunk(params, chunk, keep_maps, rates, members)
        if len(self.loop) < K:
            raise RuntimeError(
                f"async buffer cannot fill: buffer_k={K} but only "
                f"{len(self.loop)} clients in flight — raise concurrency "
                f"or dispatch more clients")
        arrivals = [self.loop.pop()[1] for _ in range(K)]
        clock = self.loop.now
        # canonical aggregation order: (dispatch version, slot) — stable
        # whatever order the clock delivered, and equal to client order
        # for a single fresh dispatch group (the sync-equivalence anchor)
        arrivals.sort(key=lambda a: (a.version, a.slot))
        for a in arrivals:
            self.in_flight_ids.discard(a.cid)
        self.last_arrived = [a.cid for a in arrivals]
        result = AsyncRoundResult(arrivals, self.version, clock,
                                  self.cfg.staleness_exponent)
        self.version += 1
        self.last_result = result
        return result


# ---------------------------------------------------------------------------
# Population driver

class AsyncPopulationSim(PopulationSim):
    """PopulationSim whose rounds are arrival buffers, not barriers.

    Each round: top the in-flight pool back up to `concurrency` by
    sampling ONLY available clients (active and not in flight — the
    store's in_flight flags are the arrival bookkeeping), dispatch them
    with the store's current rate assignments, drain one buffer, and let
    FluidServer record observations/recalibrate over the ARRIVED clients.
    Flash crowds dispatch extra clients at configured steps. Built via
    `build_population(PopulationConfig(backend="async", async_cfg=...))`.
    """

    def __init__(self, base: PopulationSim):
        self.__dict__.update(base.__dict__)
        self.acfg: AsyncConfig = self.cfg.async_cfg or AsyncConfig()
        if self.acfg.concurrency > self.cfg.n_clients:
            raise ValueError(
                f"concurrency ({self.acfg.concurrency}) exceeds the "
                f"population ({self.cfg.n_clients})")
        self.backend = AsyncBufferedBackend(
            self.model_cls, self.model_cls.UNIT_SPECS, self.acfg,
            use_kernels=self.cfg.use_kernels)

    @property
    def clock(self) -> float:
        """Virtual seconds elapsed (the async analogue of summing the
        synchronous per-round barrier times)."""
        return self.backend.loop.now

    def run_round(self, eval_now: bool = False):
        rnd = self.server.round
        need = self.acfg.concurrency - len(self.backend.in_flight_ids)
        need += sum(extra for step, extra in self.acfg.flash_crowds
                    if step == rnd)
        need = max(0, need)
        if need:
            key = jax.random.fold_in(self._key, rnd)
            ids = np.asarray(self.store.sample_cohort(key, need,
                                                      available_only=True))
            clients = self._materialize(ids)
            self.server.store = self.server.store.mark_in_flight(ids, True)
        else:
            clients = []
        self.backend.set_dispatch(clients)
        log = self.server.run_round(eval_now=eval_now, backend=self.backend)
        self.server.store = self.server.store.mark_in_flight(
            np.asarray(self.backend.last_arrived, np.int32), False)
        return log


def build_async_population(cfg, acfg: Optional[AsyncConfig] = None,
                           mesh=None) -> AsyncPopulationSim:
    """Convenience wrapper: `build_population` with backend='async'."""
    from repro.fl.population import build_population
    cfg = dataclasses.replace(cfg, backend="async",
                              async_cfg=acfg if acfg is not None
                              else cfg.async_cfg)
    return build_population(cfg, mesh=mesh)
