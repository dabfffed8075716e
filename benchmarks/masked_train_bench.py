"""Masked-train sweep -> BENCH_masked_train.json (DESIGN.md §10 gate).

Measures the *training* step of a fleet cohort at dropout rates
0.0/0.25/0.5/0.75, dense `mask * params` path vs the differentiable Pallas
kernel path (`FleetEngine(use_kernels=True)`), and reports both against the
roofline-style FLOP model: the fraction of the step's matmul FLOPs that
live in the maskable FFN determines the best-case step-time ratio at each
rate. The FLuID claim being gated: a rate-r sub-model should take ~r of
the maskable work, forward AND backward — not just the modeled sim-time.

On this CPU container the kernels run in Pallas interpret mode, which is
correctness-only (per-tile Python dispatch dominates), so the measured
interpret timings do NOT exhibit the speedup; the JSON records them for
provenance next to `flop_ratio`, the compiled-backend prediction the
acceptance gate (rate 0.5 <= 0.7x dense) applies to. On a real TPU the
same sweep (this file, interpret=False via jax.default_backend) produces
measured ratios tracking `flop_ratio`.

--smoke: tiny cohort, asserts kernel/dense delta parity and that the sweep
machinery produces a valid row (CI `kernel-grad` job).
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

import numpy as np

RATES = (0.0, 0.25, 0.5, 0.75)
GATE = {"rate": 0.5, "target_ratio": 0.7,
        "applies_on": "compiled (non-interpret) backends"}


def _build_engine(n_clients, per_client, use_kernels, seed=0):
    import jax

    from repro.fl.client import FleetClient
    from repro.fl.fleet import FleetEngine
    from repro.models.kernel_models import KernelMLP

    rng = np.random.RandomState(seed)
    x = rng.randn(n_clients * per_client, 28, 28, 1).astype(np.float32)
    y = rng.randint(0, 62, n_clients * per_client).astype(np.int32)
    clients = [FleetClient(i, KernelMLP,
                           x[i * per_client:(i + 1) * per_client],
                           y[i * per_client:(i + 1) * per_client],
                           speed=10.0, batch_size=per_client, lr=0.05,
                           local_epochs=1, seed=seed)
               for i in range(n_clients)]
    params = KernelMLP.init(jax.random.PRNGKey(seed))
    engine = FleetEngine(KernelMLP, clients, KernelMLP.UNIT_SPECS,
                         use_kernels=use_kernels)
    return engine, params


def _keep_maps(engine, rate):
    """Every client a straggler at `rate`, 128-block-aligned keep sets
    (the transformer_hooks block128 policy) so dropped blocks are whole
    skippable tiles."""
    from repro.models.kernel_models import KernelMLP
    F = KernelMLP.hidden
    kept = int(round((1.0 - rate) * F / 128)) * 128
    kept = max(kept, 128) if rate < 1.0 else 0
    km = {"ffn": np.arange(kept)}
    return {c.id: km for c in engine.clients}, kept


def _time_cohort(engine, params, keep_maps, iters=3):
    """Steady-state seconds per cohort train step (the compiled program
    only — host-side staging, the one upload of the clients' samples and
    mask-bank dedupe are excluded)."""
    import jax
    import jax.numpy as jnp

    order, ys, sw = engine._stacked_data()
    client_x = engine._client_data()
    bank, idx, _ = engine._mask_bank(params, keep_maps)
    lrs = jnp.asarray(engine.lrs)

    def once():
        out = engine._run(params, bank, idx, client_x, order, ys, sw, lrs,
                          engine.steps)
        jax.tree.leaves(out)[0].block_until_ready()
        return out
    once()                                        # compile + warm caches
    t0 = time.perf_counter()
    for _ in range(iters):
        out = once()
    return (time.perf_counter() - t0) / iters, out


def _flop_model(engine, kept_f):
    """Matmul FLOPs of one client's local step, fwd+bwd (bwd = 2x fwd for
    each matmul: dx and dW). KernelMLP: enc (784->64) and head (64->62)
    are unmaskable; the 64->F->64 FFN scales with kept_f."""
    from repro.models.kernel_models import KernelMLP
    d, F = KernelMLP.d, KernelMLP.hidden
    M = engine.bs
    fixed = 2 * M * 784 * d + 2 * M * d * 62          # fwd enc + head
    ffn = 2 * M * d * kept_f * 2                      # fwd w_in + w_out
    return 3 * (fixed + ffn), 3 * (fixed + 2 * M * d * F * 2)


def sweep(n_clients=4, per_client=16, iters=3):
    dense_eng, params = _build_engine(n_clients, per_client,
                                      use_kernels=False)
    kern_eng, _ = _build_engine(n_clients, per_client, use_kernels=True)
    rows = []
    dense_base = None
    for rate in RATES:
        keep_maps, kept = _keep_maps(dense_eng, rate)
        t_dense, out_d = _time_cohort(dense_eng, params, keep_maps, iters)
        t_kern, out_k = _time_cohort(kern_eng, params, keep_maps, iters)
        import jax
        err = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
                  for a, b in zip(jax.tree.leaves(out_d),
                                  jax.tree.leaves(out_k)))
        masked_flops, dense_flops = _flop_model(dense_eng, kept)
        if rate == 0.0:
            dense_base = t_dense
        rows.append({
            "rate": rate, "kept_neurons": kept,
            "dense_ms": round(t_dense * 1e3, 3),
            "kernel_ms": round(t_kern * 1e3, 3),
            "measured_ratio_vs_dense_r0": round(
                t_kern / dense_base, 3) if dense_base else None,
            "flop_ratio": round(masked_flops / dense_flops, 4),
            "max_delta_err": err,
        })
    return rows


def main(argv):
    import jax

    smoke = "--smoke" in argv
    if smoke:
        rows = sweep(n_clients=2, per_client=8, iters=1)
    else:
        rows = sweep()
    for r in rows:
        assert r["max_delta_err"] < 1e-4, (
            f"kernel/dense cohort divergence at rate {r['rate']}: "
            f"{r['max_delta_err']}")
    interpret = jax.default_backend() != "tpu"
    payload = {
        "bench": "masked_train",
        "model": "KernelMLP (784-enc / 64->1024->64 masked FFN / 62-head)",
        "rates": list(RATES),
        "gate": dict(GATE, predicted_kernel_ratio_at_gate_rate=next(
            r["flop_ratio"] for r in rows if r["rate"] == GATE["rate"])),
        "interpret": interpret,
        "note": ("interpret-mode CPU timings are per-tile Python dispatch, "
                 "overhead-dominated; the gate applies to flop_ratio on "
                 "compiled backends where step time tracks matmul FLOPs"
                 if interpret else
                 "compiled backend: measured ratios are the gate"),
        "jax": jax.__version__,
        "device": jax.devices()[0].platform,
        "results": rows,
    }
    at_gate = payload["gate"]["predicted_kernel_ratio_at_gate_rate"]
    assert at_gate <= GATE["target_ratio"], (
        f"FLOP model at rate {GATE['rate']} is {at_gate}, above the "
        f"{GATE['target_ratio']} gate — the maskable fraction regressed")
    if smoke:
        print(f"masked_train smoke OK: parity at rates {list(RATES)}, "
              f"flop_ratio@{GATE['rate']}={at_gate} <= "
              f"{GATE['target_ratio']}")
        return
    out = (pathlib.Path(__file__).resolve().parent.parent
           / "BENCH_masked_train.json")
    out.write_text(json.dumps(payload, indent=2) + "\n")
    print(json.dumps(payload, indent=2))


if __name__ == "__main__":
    main(sys.argv[1:])
