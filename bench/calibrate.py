#!/usr/bin/env python3
"""Readings that the limits in bench/limits/<cell>.json are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--out FILE]

For each seed, in one process (so compiles are paid once): the program's
set-up rounds exactly as a benchmark run makes them, then the plain
reference; and on the first --variant-seeds seeds the reference put in the
program's place twice more, once as the control (local SGD in bfloat16,
the precision below the configuration's float32) and once with a planted
fault (half of every batch left out, the mean taken over the rest).
Prints one JSON line per seed with the numbers of bench/harness/oracle.py
for each against the reference. No measured window is needed: training's readings
come from the set-up rounds. It needs the chip, like a run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))


def readings(cell: dict, seed: int, variants=("bf16", "half_batch")) -> dict:
    import jax
    from bench.harness import oracle
    from bench.harness.drive import Program, program_seed
    from bench.reference.fluid_ref import Reference

    config, family, traffic = cell["config"], cell["family"], cell["traffic"]
    n_rounds = int(traffic["warmup_rounds"])
    pseed = program_seed(seed)
    t0 = time.perf_counter()
    drv = Program(config, family, traffic, pseed)
    snaps = [drv.setup_round() for _ in range(n_rounds)]
    theta0 = jax.tree.map(jax.device_get, drv.theta0)
    del drv
    gc.collect()
    t1 = time.perf_counter()
    ref = Reference(config, traffic, pseed)
    recs = ref.run(theta0, n_rounds)
    t2 = time.perf_counter()
    rows = oracle.eval_rows(len(ref.y), pseed + 1)
    x, y = ref.x[rows], ref.y[rows]
    out = {"seed": seed, "program_s": t1 - t0, "reference_s": t2 - t1,
           "program": oracle.compare(family, config, theta0, snaps, recs,
                                     x, y)}
    for v in variants:
        alt = Reference(config, traffic, pseed, variant=v).run(theta0,
                                                              n_rounds)
        out[v] = oracle.compare(family, config, theta0, alt, recs, x, y)
    return out


def main(argv=None) -> int:
    sys.path.insert(0, str(ROOT / "bench"))
    import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--variant-seeds", type=int, default=3,
                    help="run the control and the fault on this many of "
                         "the first seeds")
    ap.add_argument("--out", help="also append the lines to this file")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    try:
        run.check_devices(cell["chips"], require_tpu=True)
    except run.EnvironmentRefused as e:
        print(f"bench/calibrate.py: {e}", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    for i, s in enumerate(args.seeds.split(",")):
        variants = (("bf16", "half_batch") if i < args.variant_seeds
                    else ())
        line = json.dumps(readings(cell, int(s), variants))
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
