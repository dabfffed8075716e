"""The one general generator: a traffic mix and a configuration become a
running system under test, driven through its public entry points.

  entry "cohort":     a fixed cohort of FleetClients over the benchmark's
                      own data (bench/harness/data.py), a ClientStore, the
                      traffic's RoundBackend and a FluidServer, assembled as
                      fl/simulation does; then server.run_round() per round;
  entry "population": build_population(PopulationConfig), then
                      sim.run_round() per round.

The benchmark makes the initial weights itself, on the device in one jitted
call from the seed, and hands them to the server. Everything else the
program draws from the seed it is given.

The model is the configuration's on both sides. The benchmark's side is its
family, bench/reference/<family>.py (init, rows, work counts; the CNN
family's module docstring gives the whole contract). The program's side is
its client model, `program_model`, in one of two forms: a key of
`repro.models.small.MODELS`, or `"package.module:Attr"`, a model class
imported from the program's source by that path.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Dict, List

import jax
import numpy as np

from bench.harness.data import cohort_data
from bench.reference.fluid_ref import RoundRecord


def program_model(name: str):
    """The program's client model class a configuration names."""
    if ":" in name:
        module, attr = name.split(":", 1)
        return getattr(importlib.import_module(module), attr)
    from repro.models.small import MODELS
    return MODELS[name]


def program_seed(seed: int) -> int:
    """A 31-bit seed for the program's generators from any whole number."""
    state = np.random.SeedSequence(seed % 2 ** 64).generate_state(1)[0]
    return int(state % (2 ** 31 - 1))


class Program:
    """The system under test, built from a configuration and a traffic mix
    and stepped one FLuID round at a time."""

    def __init__(self, config: dict, family, traffic: dict, pseed: int):
        self.config, self.family, self.traffic = config, family, traffic
        self.rate = float(traffic["fixed_rate"])
        self.population = traffic["entry"] == "population"
        init = jax.jit(functools.partial(family.init, config))
        self.theta0 = init(jax.random.PRNGKey(pseed))
        if self.population:
            self.sim = self._build_population(pseed)
            self.server = self.sim.server
            self.server.params = self.theta0
            bs, lr = self.sim.batch_size, self.sim.lr
            if bs != config["batch_size"] or not np.isclose(lr, config["lr"]):
                raise ValueError(
                    f"the program's {config['program_workload']} workload "
                    f"trains at batch {bs}, lr {lr}; the configuration "
                    f"states batch {config['batch_size']}, lr {config['lr']}")
        else:
            self.clients, self.server = self._build_cohort(pseed)
        self.plans: List[List[int]] = []

    # ------------------------------------------------------------ build
    def _build_cohort(self, pseed):
        from repro.core.fluid import FluidConfig, FluidServer
        from repro.fl.client import FleetClient
        from repro.fl.population import ClientStore
        from repro.fl.rounds import make_backend
        t, cfg = self.traffic, self.config
        model_cls = program_model(cfg["program_model"])
        x, y, parts, speeds = cohort_data(cfg, t, pseed)
        n = len(parts)
        clients = [FleetClient(i, model_cls, x[parts[i]], y[parts[i]],
                               speed=float(speeds[i]),
                               batch_size=int(cfg["batch_size"]),
                               lr=float(cfg["lr"]),
                               local_epochs=int(t["local_epochs"]),
                               seed=pseed)
                   for i in range(n)]
        store = ClientStore.empty(n).register(
            np.arange(n), speeds.astype(np.float32), np.arange(n))
        backend = make_backend(t["backend"], model_cls, clients,
                               model_cls.UNIT_SPECS,
                               n_shards=t.get("n_shards"))
        fcfg = FluidConfig(method=t["policy"], fixed_rate=t["fixed_rate"],
                           seed=pseed)
        return clients, FluidServer(self.theta0, model_cls.UNIT_SPECS,
                                    backend, fcfg, store=store)

    def _build_population(self, pseed):
        from repro.fl.population import PopulationConfig, build_population
        t = self.traffic
        cfg = PopulationConfig(
            n_clients=t["n_clients"], cohort_size=t["cohort_size"],
            workload=self.config["program_workload"], backend=t["backend"],
            policy=t["policy"], n_partitions=t["n_partitions"],
            samples_per_partition=t["samples_per_partition"],
            straggler_frac_pop=t["straggler_frac_pop"],
            slow_factor=t["slow_factor"], local_epochs=t["local_epochs"],
            fixed_rate=t["fixed_rate"], n_shards=t.get("n_shards"),
            seed=pseed)
        return build_population(cfg)

    # ------------------------------------------------------------ rounds
    def cohort(self, rnd: int) -> List[int]:
        if self.population:
            return [int(c) for c in self.sim.cohort_ids(rnd)]
        return [c.id for c in self.clients]

    def step(self):
        """One FLuID round through the program's entry point; the new
        global params are ready when it returns."""
        log = (self.sim.run_round() if self.population
               else self.server.run_round())
        jax.block_until_ready(self.server.params)
        plan = self.server.plan
        self.plans.append(list(plan.stragglers) if plan else [])
        return log

    def setup_round(self) -> RoundRecord:
        """A set-up round, with what the correctness check compares (the
        same record the reference keeps of its rounds)."""
        rnd = self.server.round
        cohort = self.cohort(rnd)
        rates = self.server.store.rates_of(cohort)
        sub = ([c for c, r in zip(cohort, rates) if r < 1.0]
               if rnd >= 1 else [])
        self.step()
        return RoundRecord(
            params=jax.tree.map(np.asarray, self.server.params),
            cohort=cohort, sub_model_clients=sub,
            stragglers=list(self.plans[-1]),
            keep_next={g: np.asarray(k) for g, k in
                       self.server.policy.keep_map(self.rate).items()})

    def warm_sub_model_counts(self, lo: int, hi: int):
        """Compile what a later round can meet that the set-up rounds may
        not have. The gather of the non-straggler clients' deltas and the
        statistics program over them compile anew for every count of
        clients that trained a sub-model (the non-stragglers' count is
        their leading axis), and that count moves from round to round as
        clients near the straggler band flip. So run one cohort round
        through the public backend API with a sub-model in the bank, then
        the statistics with lo..hi of its clients counted as sub-model
        clients, each followed by the calibration's host passes (NumPy,
        which compile nothing). The server's state is untouched."""
        from repro.core import invariant as inv
        from repro.fl.rounds import make_backend
        if self.population:
            sim = self.sim
            ids = [int(c) for c in sim.cohort_ids()]
            backend = make_backend(sim.cfg.backend, sim.model_cls,
                                   sim._materialize(np.asarray(ids)),
                                   sim.model_cls.UNIT_SPECS,
                                   use_kernels=sim.cfg.use_kernels,
                                   mesh=sim.mesh, n_shards=sim.cfg.n_shards)
        else:
            ids, backend = self.cohort(0), self.server.backend
        params = self.server.params
        keep = self.server.policy.keep_map(self.rate)
        res = backend.run_round(params, {ids[0]: keep}, {ids[0]: self.rate})
        jax.block_until_ready(res.aggregate(params))
        for k in range(lo, hi + 1):
            stats = dataclasses.replace(
                res, straggler_ids=frozenset(ids[:k])).non_straggler_stats(
                    params)
            inv.initial_threshold(stats)
            inv.calibrate_threshold(stats, 1, 1e30)
            inv.mean_stats(stats)

    # ------------------------------------------------------------ counts
    def round_work(self, first: int, n: int):
        """[(samples, flops, min_bytes)] of rounds first..first+n-1, from
        the cohorts and the plans the program made (a straggler trains the
        sub-model from the round after a calibration flags it)."""
        fam, cfg = self.family, self.config
        flops = {1.0: fam.train_flops(cfg, 1.0),
                 self.rate: fam.train_flops(cfg, self.rate)}
        par = {1.0: fam.param_count(cfg, 1.0),
               self.rate: fam.param_count(cfg, self.rate)}
        sbytes = fam.sample_bytes(cfg)
        if self.population:
            t = self.traffic
            spp = int(t["samples_per_partition"])
            bs = min(self.sim.batch_size, spp)
            per_client = int(t["local_epochs"]) * (spp // bs) * bs
            samples_of = lambda cid: per_client      # noqa: E731
        else:
            by_id = {c.id: c.local_epochs * (c.n_samples // c.eff_batch_size)
                     * c.eff_batch_size for c in self.clients}
            samples_of = by_id.__getitem__
        rate: Dict[int, float] = {}
        out = []
        for rnd in range(first + n):
            cohort = self.cohort(rnd)
            if rnd >= first:
                s = f = b = 0.0
                for cid in cohort:
                    r = rate.get(cid, 1.0) if rnd >= 1 else 1.0
                    k = samples_of(cid)
                    s += k
                    f += k * flops[r]
                    b += 8.0 * par[r] + k * sbytes
                out.append((int(s), f, b))
            strag = set(self.plans[rnd])
            for cid in cohort:
                rate[cid] = self.rate if cid in strag else 1.0
        return out
