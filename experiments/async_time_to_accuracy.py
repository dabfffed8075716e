"""Async buffered rounds vs the synchronous barrier, in simulated seconds.

Writes experiments/async_time_to_accuracy.json. Run from the repo root:

    PYTHONPATH=src python -m experiments.async_time_to_accuracy [--smoke]

The claim under test (DESIGN.md §13): under heavy-tailed client latencies,
asynchronous buffered aggregation (fl/async_rounds.py) reaches the
synchronous fleet's round-40 accuracy in strictly less SIMULATED wall-clock,
because the barrier pays max-of-cohort lognormal latency every round while
the buffer pays the K-th order statistic of a larger in-flight pool.

Both arms share the identical population: same ClientStore speeds (10%
slow-band stragglers), same per-client lognormal tail (PopulationConfig.
tail_sigma — applied in SimClient._sim_time, so the barrier baseline
experiences the same latency distribution, not a handicapped copy), same
invariant-dropout calibration. Time is emulated seconds from the client
speed model: sum of per-round barrier maxima for sync, the EventLoop clock
for async. Real (host) seconds are recorded for provenance only.

--devices N   force N virtual host devices (must be first; set before jax
              imports so the flag takes effect).
--smoke       ~2 min CI mode: small store, short horizon, asserts the
              async arm actually reaches the sync target accuracy.
"""
from __future__ import annotations

import json
import math
import os
import pathlib
import sys
import time

# Must happen before anything imports jax.
if "--devices" in sys.argv:
    _n = int(sys.argv[sys.argv.index("--devices") + 1])
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_n}").strip()

SYNC_ROUNDS = 40
TAIL_SIGMA = 0.6


def _base_cfg(smoke: bool):
    from repro.fl.population import PopulationConfig
    if smoke:
        return dict(n_clients=2_000, cohort_size=16, workload="synth",
                    n_partitions=16, samples_per_partition=40,
                    straggler_frac_pop=0.1, tail_sigma=TAIL_SIGMA, seed=0), \
            PopulationConfig
    return dict(n_clients=20_000, cohort_size=32, workload="synth",
                n_partitions=64, samples_per_partition=100,
                straggler_frac_pop=0.1, tail_sigma=TAIL_SIGMA, seed=0), \
        PopulationConfig


def run_sync(base, PopulationConfig, rounds):
    from repro.fl.population import build_population
    sim = build_population(PopulationConfig(backend="fleet", **base))
    t0 = time.perf_counter()
    hist = sim.run(rounds, eval_every=max(1, rounds // 8))
    real = time.perf_counter() - t0
    accs = [(h.round, h.accuracy) for h in hist if not math.isnan(h.accuracy)]
    return {
        "backend": "fleet",
        "rounds": rounds,
        "client_updates": rounds * base["cohort_size"],
        "sim_seconds": round(sum(h.round_time for h in hist), 2),
        "final_accuracy": round(accs[-1][1], 4),
        "accuracy_trajectory": [(r, round(a, 4)) for r, a in accs],
        "real_seconds": round(real, 1),
    }


def run_async(base, PopulationConfig, target_acc, buffer_k, concurrency,
              max_buffers, eval_every=2):
    from repro.fl.async_rounds import AsyncConfig
    from repro.fl.population import build_population
    from repro.core.straggler import ArrivalModel
    acfg = AsyncConfig(buffer_k=buffer_k, concurrency=concurrency,
                       staleness_exponent=0.5,
                       arrival=ArrivalModel())   # tails live client-side
    sim = build_population(PopulationConfig(backend="async",
                                            async_cfg=acfg, **base))
    t0 = time.perf_counter()
    accs, reached_at = [], None
    for step in range(max_buffers):
        log = sim.run_round(eval_now=(step % eval_every == eval_every - 1))
        if not math.isnan(log.accuracy):
            accs.append((step, round(log.accuracy, 4)))
            if log.accuracy >= target_acc:
                reached_at = step
                break
    real = time.perf_counter() - t0
    hist = sim.server.history
    return {
        "backend": "async",
        "buffer_k": buffer_k,
        "concurrency": concurrency,
        "staleness_exponent": acfg.staleness_exponent,
        "buffers": len(hist),
        "client_updates": len(hist) * buffer_k,
        "sim_seconds": round(sim.clock, 2),
        "target_accuracy": round(target_acc, 4),
        "reached_target": reached_at is not None,
        "reached_at_buffer": reached_at,
        "final_accuracy": accs[-1][1] if accs else None,
        "accuracy_trajectory": accs[-12:],
        "staleness_max": max(h.staleness_max for h in hist),
        "staleness_mean_last": round(hist[-1].staleness_mean, 3),
        "dropouts": sim.backend.total_drops,
        "real_seconds": round(real, 1),
    }


def main(argv):
    import jax
    smoke = "--smoke" in argv
    base, PopulationConfig = _base_cfg(smoke)
    rounds = 8 if smoke else SYNC_ROUNDS
    print(f"sync arm: {rounds} barrier rounds, cohort "
          f"{base['cohort_size']}, tail_sigma={TAIL_SIGMA}",
          file=sys.stderr)
    sync = run_sync(base, PopulationConfig, rounds)
    print(f"  sync: acc={sync['final_accuracy']} in "
          f"{sync['sim_seconds']} sim s", file=sys.stderr)
    k = base["cohort_size"] // 2
    async_row = run_async(base, PopulationConfig, sync["final_accuracy"],
                          buffer_k=k, concurrency=4 * base["cohort_size"],
                          max_buffers=40 if smoke else 10 * rounds)
    print(f"  async: acc={async_row['final_accuracy']} in "
          f"{async_row['sim_seconds']} sim s "
          f"({async_row['buffers']} buffers, "
          f"max staleness {async_row['staleness_max']})", file=sys.stderr)

    assert async_row["reached_target"], (
        "async arm never reached the sync target accuracy — raise "
        "max_buffers or check staleness weighting", async_row)
    speedup = sync["sim_seconds"] / async_row["sim_seconds"]
    if smoke:
        print(f"async smoke OK: target {sync['final_accuracy']} reached at "
              f"buffer {async_row['reached_at_buffer']}, "
              f"sim speedup x{speedup:.2f}")
        return
    assert async_row["sim_seconds"] < sync["sim_seconds"], (
        "acceptance: async must reach the sync round-40 accuracy in "
        "strictly less simulated wall-clock", sync, async_row)

    payload = {
        "bench": "async",
        "store_clients": base["n_clients"],
        "workload": "synth (32-d MLP)",
        "tail_sigma": TAIL_SIGMA,
        "straggler_frac_pop": base["straggler_frac_pop"],
        "sim_speedup_to_target": round(speedup, 2),
        "note": ("simulated seconds from the shared client speed model: "
                 "sync pays max-of-cohort lognormal latency per round, "
                 "async pays the K-th arrival of a "
                 f"{4 * base['cohort_size']}-client in-flight pool"),
        "jax": jax.__version__,
        "device": jax.devices()[0].platform,
        "results": [sync, async_row],
    }
    out = pathlib.Path(__file__).resolve().with_suffix(".json")
    out.write_text(json.dumps(payload, indent=2) + "\n")
    print(json.dumps(payload, indent=2))


if __name__ == "__main__":
    main(sys.argv[1:])
