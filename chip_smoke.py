#!/usr/bin/env python3
"""Chip smoke test: the FLuID federated training path, end to end, on a TPU.

    python chip_smoke.py              # one chip: phases a-c
    python chip_smoke.py --chips 4    # four chips: the sharded-fleet phase only

Everything runs in this one process, through the entry points a user calls
(`run_experiment`, `build_population`), with weights and data made from a
seed. Each phase prints one JSON line with its wall and compile seconds
(set-up, not speed) and the values its checks compared. The last line is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

and is printed only when every check of every phase held. Without a TPU,
or outside a checkout of the repository, the script exits non-zero and
prints no result.

Phases (one chip):
  a. Synchronous FLuID rounds of the paper's FEMNIST CNN on the fleet
     backend (5 local epochs per round): accuracy rises, planted stragglers
     get sub-models (rate < 1), and the fleet matches the sequential
     reference on the paper's 5-client cohort under float32 ("highest")
     matmul precision.
  b. The compiled Pallas path: the kernel workloads with use_kernels on and
     off from one seed agree; dropped units' deltas are exact zeros; the
     cohort program was lowered with Mosaic kernels (`tpu_custom_call`).
  c. A 10^5-client population round on the device: the ClientStore lives on
     the TPU and ~10% of each cohort is flagged.
Phase (four chips):
  shards. `sharded_fleet` at S=4 shards on a 4-device `data` mesh against
     the same S=4 program on one device, from one seed.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
sys.path.insert(0, str(SRC))

# Tolerances, max |a - b| over every parameter, established on a TPU v5e
# (the measured values are printed with each phase).
# SEQ_ATOL: fleet vs sequential, 2 rounds, 5 clients. On the TPU the two
# programs round differently (one batched convolution for the cohort
# against one per client), and a rounding difference can flip a ReLU or
# max-pool choice, which moves an SGD step by about lr * |grad|. Measured
# 5.9e-4 under "highest" precision, the size of the sequential reference's
# own difference between the TPU and the host CPU (5.2e-4); on the CPU the
# two paths agree to 3e-8 because they round alike. The reference itself
# amplifies: on the CPU, scaling the initial weights by (1 + 2^-16) moves
# its result by 7.6e-4, and by (1 + 2^-9) by 1.2e-3. So this comparison
# catches a wrong program, not a lost bit of precision; phase b's one-round
# kernel-vs-dense comparison is the tight one.
# KERNEL_ATOL: use_kernels=True vs False from one seed; measured 6e-8.
SEQ_ATOL = 1e-3
KERNEL_ATOL = 1e-4
# SHARD_ATOL: S=4 on 4 devices vs S=4 on 1 device, 2 rounds. The synth run
# is well conditioned (on the CPU, scaling its initial weights by
# (1 + 2^-23) moves the result by 6e-8), so an all-reduce that adds the
# four partials in another order than the local chain stays near 1e-7.
SHARD_ATOL = 1e-5


class SmokeFailure(RuntimeError):
    pass


def _check(ok, what: str):
    if not ok:
        raise SmokeFailure(what)


def _max_abs_diff(a, b) -> float:
    import jax
    import numpy as np
    return max(float(np.max(np.abs(np.asarray(x, np.float64)
                                   - np.asarray(y, np.float64))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _bitwise_equal(a, b) -> bool:
    import jax
    import numpy as np
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# phase a: synchronous rounds at the paper's model widths

def phase_femnist(n_clients=32, per_client=200, rounds=4, local_epochs=5,
                  n_stragglers=4, ref_clients=5, ref_rounds=2,
                  seed=0) -> dict:
    import jax
    from repro.fl.simulation import (CohortConfig, SimulationConfig,
                                     run_experiment)

    planted = tuple(range(n_stragglers))
    cfg = SimulationConfig(
        workload="femnist", backend="fleet", seed=seed,
        cohort=CohortConfig(n_clients=n_clients, straggler_ids=planted,
                            n_data=n_clients * per_client,
                            local_epochs=local_epochs))
    sim, hist = run_experiment(cfg, rounds=rounds, eval_every=1)
    acc = [h.accuracy for h in hist]
    _check(all(math.isfinite(a) for a in acc), f"accuracy not finite: {acc}")
    _check(acc[-1] > acc[0], f"accuracy did not rise over round 0: {acc}")
    rates = sim.server.plan.rates
    _check(all(rates.get(c, 1.0) < 1.0 for c in planted),
           f"planted stragglers {planted} not all on sub-models: {rates}")
    trained = sorted({c for h in hist[1:] for c in h.stragglers})

    def ref_run(backend):
        rc = SimulationConfig(
            workload="femnist", backend=backend, seed=seed,
            cohort=CohortConfig(n_clients=ref_clients,
                                n_data=ref_clients * per_client))
        return run_experiment(rc, rounds=ref_rounds)

    with jax.default_matmul_precision("highest"):
        seq, hs = ref_run("sequential")
        flt, hf = ref_run("fleet")
    _check([h.stragglers for h in hs] == [h.stragglers for h in hf]
           and [h.rates for h in hs] == [h.rates for h in hf],
           "fleet and sequential planned different stragglers or rates")
    _check(any(h.rates for h in hs[1:]),
           "the reference rounds trained no sub-model")
    diff = _max_abs_diff(seq.server.params, flt.server.params)
    _check(diff <= SEQ_ATOL,
           f"fleet vs sequential params differ by {diff} > {SEQ_ATOL}")
    return {"accuracy": acc, "planted": list(planted),
            "rates": {str(c): r for c, r in sorted(rates.items())},
            "sub_model_clients": trained,
            "fleet_vs_sequential_max_abs": diff, "atol": SEQ_ATOL}


# ---------------------------------------------------------------------------
# phase b: the compiled Pallas kernel path

def _half_keep_map(model_cls):
    """Keep the first half of every droppable group: whole 128-neuron
    blocks of the FFN and whole heads, so kernel tiles are skipped."""
    import numpy as np
    return {g["name"]: np.arange(g["size"] // 2) for g in model_cls.UNIT_SPECS}


def phase_kernels(workload="femnist_kernel", n_clients=32, per_client=200,
                  rounds=4, n_stragglers=4, seed=0) -> dict:
    import jax
    import numpy as np
    from repro.fl.simulation import (CohortConfig, SimulationConfig,
                                     run_experiment)
    from repro.kernels import ops

    planted = tuple(range(n_stragglers))
    sims = {}
    for use_kernels in (True, False):
        cfg = SimulationConfig(
            workload=workload, backend="fleet", use_kernels=use_kernels,
            seed=seed,
            cohort=CohortConfig(n_clients=n_clients, straggler_ids=planted,
                                n_data=n_clients * per_client))
        with jax.default_matmul_precision("highest"):
            sims[use_kernels], _ = run_experiment(cfg, rounds=rounds)
    hk, hd = sims[True].server.history, sims[False].server.history
    _check([h.stragglers for h in hk] == [h.stragglers for h in hd],
           "kernel and dense runs planned different stragglers")
    diff = _max_abs_diff(sims[True].server.params, sims[False].server.params)
    _check(diff <= KERNEL_ATOL,
           f"kernel vs dense params differ by {diff} > {KERNEL_ATOL}")

    # one more round through both paths from the same params, with a
    # block-structured sub-model for the planted stragglers: dropped
    # blocks/heads come back as exact zeros from the kernel program, and
    # the aggregates agree without the plan's feedback in between
    params = sims[False].server.params
    keep = _half_keep_map(sims[True].model_cls)
    out = {}
    for use_kernels, sim in sims.items():
        with jax.default_matmul_precision("highest"):
            out[use_kernels] = sim.server.engine.run_cohort(
                params, {c: keep for c in planted},
                {c: 0.5 for c in planted})
    round_diff = _max_abs_diff(out[True].aggregate(params),
                               out[False].aggregate(params))
    _check(round_diff <= KERNEL_ATOL,
           f"kernel vs dense aggregate of one round differs by "
           f"{round_diff} > {KERNEL_ATOL}")
    eng, res = sims[True].server.engine, out[True]
    rows = np.asarray(res.mask_idx)
    n_zero = 0
    for d, b in zip(jax.tree.leaves(res.deltas),
                    jax.tree.leaves(res.mask_bank)):
        d, b = np.asarray(d), np.asarray(b)
        for i in np.flatnonzero(rows):
            dropped = b[rows[i]] == 0
            _check(not np.any(d[i][dropped]),
                   f"client {i}: non-zero delta on a dropped unit")
            n_zero += int(dropped.sum())
    _check(n_zero > 0, "the block-structured keep map dropped nothing")

    C = len(eng.clients)
    x0 = eng.clients[0].x
    sds = jax.ShapeDtypeStruct
    lowered = eng._run.lower(
        params, res.mask_bank, res.mask_idx,
        sds((C, eng.rows) + x0.shape[1:], x0.dtype),
        sds((C, eng.steps, eng.bs), np.int32),
        sds((C, eng.steps, eng.bs), np.int32),
        sds((C, eng.steps, eng.bs), np.float32),
        sds((C,), np.float32), n_steps=eng.steps).as_text()
    compiled = "tpu_custom_call" in lowered
    _check(compiled == (not ops._default_interpret()),
           f"cohort program lowered with Mosaic kernels: {compiled}, "
           f"interpret mode: {ops._default_interpret()}")
    return {"workload": workload, "kernel_vs_dense_max_abs": diff,
            "one_round_aggregate_max_abs": round_diff,
            "atol": KERNEL_ATOL, "dropped_coords_exact_zero": n_zero,
            "tpu_custom_call": compiled,
            "accuracy": [hk[-1].accuracy, hd[-1].accuracy]}


# ---------------------------------------------------------------------------
# phase c: a population round on the device

def phase_population(n_clients=100_000, cohort=1000, rounds=3, seed=0) -> dict:
    import jax
    from repro.fl.population import PopulationConfig, build_population
    from repro.launch.mesh import make_host_mesh

    cfg = PopulationConfig(n_clients=n_clients, cohort_size=cohort,
                           workload="synth", backend="sharded_fleet",
                           n_shards=1, seed=seed)
    sim = build_population(cfg, mesh=make_host_mesh(data=1))
    flagged = []
    for r in range(rounds):
        log = sim.run_round(eval_now=r == rounds - 1)
        flagged.append(len(sim.server.plan.stragglers) / cohort)
    _check(sim.store.n_active == n_clients,
           f"store holds {sim.store.n_active} active clients, "
           f"not {n_clients}")
    _check(all(0.05 <= f <= 0.15 for f in flagged),
           f"flagged share of each cohort not ~10%: {flagged}")
    _check(math.isfinite(log.accuracy), f"accuracy {log.accuracy}")
    dev = jax.devices()[0]
    placed = {str(d) for a in jax.tree.leaves(sim.store) for d in a.devices()}
    _check(placed == {str(dev)},
           f"ClientStore arrays on {placed}, not on {dev}")
    return {"n_active": sim.store.n_active, "flagged_share": flagged,
            "store_devices": sorted(placed), "accuracy": log.accuracy}


# ---------------------------------------------------------------------------
# four chips: the sharded fleet across devices

def phase_shards(n_clients=100_000, cohort=1000, rounds=2, n_shards=4,
                 seed=0) -> dict:
    import jax
    from repro.fl.population import PopulationConfig, build_population
    from repro.fl.rounds import make_backend
    from repro.launch.mesh import make_host_mesh

    n_dev = len(jax.devices())
    _check(n_dev >= n_shards, f"{n_shards} shards need {n_shards} devices, "
                              f"found {n_dev}")
    cfg = PopulationConfig(n_clients=n_clients, cohort_size=cohort,
                           workload="synth", backend="sharded_fleet",
                           n_shards=n_shards, seed=seed)
    runs = {}
    # mesh=None takes the defaults a user gets: one shard per device
    for name, mesh in (("spread", None), ("one_device", make_host_mesh(1))):
        sim = build_population(cfg, mesh=mesh)
        cohorts = []
        for _ in range(rounds):
            cohorts.append(sim.cohort_ids().tolist())
            sim.run_round()
        runs[name] = (cohorts, sim)
    (c_a, sim_a), (c_b, sim_b) = runs["spread"], runs["one_device"]
    _check(c_a == c_b, "cohorts differ between the device layouts")
    _check([h.stragglers for h in sim_a.server.history]
           == [h.stragglers for h in sim_b.server.history],
           "straggler plans differ between the device layouts")
    diff = _max_abs_diff(sim_a.server.params, sim_b.server.params)
    bitwise = _bitwise_equal(sim_a.server.params, sim_b.server.params)
    _check(diff <= SHARD_ATOL, f"params differ by {diff} > {SHARD_ATOL}")

    # the default backend puts one shard on each device, not all on one
    ids = sim_a.cohort_ids()
    backend = make_backend("sharded_fleet", sim_a.model_cls,
                           sim_a._materialize(ids), sim_a.model_cls.UNIT_SPECS)
    res = backend.run_round(sim_a.server.params, {}, {})
    leaf = jax.tree.leaves(res.deltas)[0]
    devs = {str(s.device) for s in leaf.addressable_shards}
    _check(backend.engine.mesh.shape["data"] == n_dev and len(devs) == n_dev,
           f"default sharded_fleet spans {sorted(devs)} on a "
           f"{n_dev}-device host")
    return {"n_shards": n_shards, "devices": n_dev,
            "cohorts_equal": True, "params_bitwise_equal": bitwise,
            "params_max_abs": diff, "atol": SHARD_ATOL,
            "delta_shard_devices": sorted(devs)}


# ---------------------------------------------------------------------------
# driver

ONE_CHIP = (("a_femnist_rounds", phase_femnist, {}),
            ("b_kernels_mlp", phase_kernels, {"workload": "femnist_kernel"}),
            ("b_kernels_attn", phase_kernels, {"workload": "femnist_attn"}),
            ("c_population", phase_population, {}))
FOUR_CHIPS = (("shards", phase_shards, {}),)


def run_phases(phases) -> None:
    """Run each phase and print its line; the first failure propagates."""
    import jax
    compile_s = [0.0]

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        for name, fn, kw in phases:
            t0, c0 = time.perf_counter(), compile_s[0]
            checks = fn(**kw)
            print(json.dumps({"phase": name,
                              "wall_s": time.perf_counter() - t0,
                              "compile_s": compile_s[0] - c0,
                              "checks": checks}), flush=True)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-fleet phase across chips")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository source at {SRC}; run it from the "
              f"root of a checkout", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 1
    enable_compile_cache()
    run_phases(FOUR_CHIPS if args.chips == 4 else ONE_CHIP)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
