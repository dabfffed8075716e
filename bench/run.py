#!/usr/bin/env python3
"""One run of one benchmark cell on the accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of BENCHMARK.json names a configuration (bench/configs/<name>.json,
whose `family` is a plain model in bench/reference/) and a traffic mix
(bench/traffic/<traffic>.json, read by the one generator in
bench/harness/drive.py). Each metric is a reader in bench/metrics/<name>.py.

A run: build the system under test from the seed, with weights made on the
device in one jitted call; run the set-up rounds through the window's own
call (they compile, or load from the compile cache, every shape the window
uses); then drive whole FLuID rounds for --seconds. With --trace 1 the
window runs under the profiler and the line carries the per-layer metrics;
with --trace 0, the end-to-end ones. After the window the program's state is
freed and the plain reference follows the set-up rounds; the comparison
(bench/harness/oracle.py) against bench/limits/<cell>.json decides
`correct`. The last line of standard output is the result, as JSON.

It exits non-zero and prints no result when JAX finds no TPU, fewer chips
than the cell asks for, a device missing from bench/peaks.json, or no
program source beside the benchmark.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_DIR = ".jax_cache"


class EnvironmentRefused(RuntimeError):
    """The run cannot be measured here; no result is printed."""


def load_cell(name: str) -> dict:
    """The cell's entry, configuration, family, traffic, metrics, limits."""
    from bench.harness.data import family_of
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())

    def used(m):
        return "workloads" not in m or name in m["workloads"]
    return {
        "name": name, "chips": int(cell["chips"]), "config": config,
        "family": family_of(config),
        "traffic": json.loads(
            (BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
        "end_to_end": [m for m in bm["end_to_end"] if used(m)],
        "per_layer": [m for m in bm["per_layer"] if used(m)],
        "limits": json.loads(
            (BENCH / "limits" / f"{name}.json").read_text())["limits"],
    }


def enable_compile_cache():
    """JAX's persistent compile cache at a fixed path inside the checkout,
    whatever the environment names, with every program kept (no size cap,
    no minimum compile time), so that only a cell's first run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def check_devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise EnvironmentRefused(
            f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise EnvironmentRefused(
            f"the cell needs {chips} chips; JAX found {len(devs)}")
    peaks = json.loads((BENCH / "peaks.json").read_text())
    kind = devs[0].device_kind
    if kind not in peaks:
        if require_tpu:
            raise EnvironmentRefused(
                f"device kind {kind!r} is not in bench/peaks.json")
        kind = next(iter(peaks))
    return devs[:chips], peaks[kind]


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, log=print) -> dict:
    """Set-up, window and check of one cell; returns the result line."""
    if not (ROOT / "src" / "repro").is_dir():
        raise EnvironmentRefused(f"no program source at {ROOT / 'src'}")
    import jax
    from bench.harness import oracle
    from bench.harness.context import RoundStat, RunContext
    from bench.harness.drive import Program, program_seed
    from bench.harness.trace import (ROUND_MARK, breakdown, events_of,
                                     find_xplane, summarize)
    from bench.reference.fluid_ref import Reference

    devs, peak = check_devices(cell["chips"], require_tpu)
    enable_compile_cache()
    compiles = {"on": False, "n": 0}

    def on_duration(event, secs, **_):
        if event == COMPILE_EVENT and compiles["on"]:
            compiles["n"] += 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    config, family, traffic = cell["config"], cell["family"], cell["traffic"]
    pseed = program_seed(seed)
    drv = Program(config, family, traffic, pseed)
    snaps = [drv.setup_round() for _ in range(int(traffic["warmup_rounds"]))]
    drv.warm_sub_model_counts(*traffic["warm_sub_model_counts"])
    first = drv.server.round
    setup_s = time.perf_counter() - T_START

    trace_dir = tempfile.TemporaryDirectory() if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir.name, profiler_options=opts)
    walls, calib = [], []
    compiles["on"] = True
    t0 = t = time.perf_counter()
    while True:
        if trace:
            with jax.profiler.TraceAnnotation(ROUND_MARK):
                rlog = drv.step()
        else:
            rlog = drv.step()
        t_prev, t = t, time.perf_counter()
        walls.append(t - t_prev)
        calib.append(rlog.calib_time)
        if t - t0 >= seconds:
            break
    compiles["on"] = False
    window_s = t - t0
    summary = None
    if trace:
        jax.profiler.stop_trace()
        summary = summarize(events_of(find_xplane(trace_dir.name)))
        trace_dir.cleanup()
    jax.monitoring.unregister_event_duration_listener(on_duration)

    work = drv.round_work(first, len(walls))
    ctx = RunContext(
        cell=cell["name"], chips=cell["chips"],
        peak_flops=float(peak["flops_per_s"]),
        peak_bytes_per_s=float(peak["hbm_bytes_per_s"]), setup_s=setup_s,
        window_s=window_s, compiles_in_window=compiles["n"], trace=summary,
        rounds=[RoundStat(w, s, f, b, c) for w, (s, f, b), c
                in zip(walls, work, calib)])
    stats = [d.memory_stats() or {} for d in devs]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                       for s in stats)}
    metrics = {}
    for m in (cell["per_layer"] if trace else cell["end_to_end"]):
        v = importlib.import_module(f"bench.metrics.{m['name']}").read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    if trace:
        from bench.metrics import cohort_sgd_roofline
        _, which = cohort_sgd_roofline.bound(ctx)
        log(f"cohort_sgd_roofline is bound by {which}", file=sys.stderr)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s

    # ---- correctness: free the program's state, then the reference
    theta0 = jax.tree.map(lambda a: jax.device_get(a), drv.theta0)
    del drv
    gc.collect()
    ref = Reference(config, traffic, pseed)
    recs = ref.run(theta0, len(snaps))
    rows = oracle.eval_rows(len(ref.y), pseed + 1)
    numbers = oracle.compare(family, config, theta0, snaps, recs,
                             ref.x[rows], ref.y[rows])
    correct, checked = oracle.judge(numbers, cell["limits"])
    result = {"correct": correct, "attempted": len(walls), "failed": 0,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = breakdown(summary)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, v, lim in checked}
    for k, v, lim in checked:
        log(f"check {k} {v!r} limit {lim!r}", file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "BENCHMARK.json").is_file():
        print("no BENCHMARK.json at the checkout root", file=sys.stderr)
        return 2
    cell = load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except EnvironmentRefused as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
