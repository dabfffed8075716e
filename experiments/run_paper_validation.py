"""Paper-validation pass -> experiments/paper_validation.json (incremental).

Reproduces (at CPU-feasible scale) the claims of: Table 2 (accuracy:
Random/Ordered/Invariant x r), Fig 4a (straggler time), Fig 4b (dynamic
stragglers), Fig 5 (scalability), Fig 6 (invariant evolution), Table 3
(threshold sweep). Results are flushed after every experiment. Scale knobs
are sized for a single CPU core; pass --full for the bigger pass.
"""
import json
import sys
import time

from experiments import paper_experiments as pe

FULL = "--full" in sys.argv
OUT = "experiments/paper_validation.json"
results = {}
t0 = time.time()


def flush(name, value):
    results[name] = value
    results["wall_s"] = round(time.time() - t0, 1)
    with open(OUT, "w") as f:
        json.dump(results, f, indent=2, default=str)
    print(name, "done", results["wall_s"], flush=True)


flush("fig6_invariant_evolution",
      pe.fig6_invariant_evolution(rounds=20, n_data=800))
flush("fig4a_straggler_time", pe.fig4a_straggler_time(rounds=10, n_data=600))
flush("fig4b_dynamic", pe.fig4b_dynamic_stragglers(rounds=16, n_data=500))
flush("table3_threshold",
      pe.table3_threshold(rounds=6, n_data=600,
                          thresholds=(0.002, 0.005, 0.01, 0.02, 0.05)))

rates = (0.95, 0.75, 0.5) if FULL else (0.75, 0.5)
t2 = {f"{m}@r{r}": v for (m, r), v in pe.table2_accuracy(
    rates=rates, rounds=30 if FULL else 20,
    n_data=1500 if FULL else 1000,
    seeds=(0, 1) if FULL else (0,)).items()}
flush("table2_accuracy_femnist", t2)

flush("fig5_scalability",
      pe.fig5_scalability(n_clients=16 if FULL else 10,
                          rounds=15 if FULL else 10,
                          n_data=2000 if FULL else 1200))

t2s = {f"{m}@r{r}": v for (m, r), v in pe.table2_accuracy(
    workload="shakespeare", rates=(0.75,), rounds=15, n_data=1000,
    seeds=(0,)).items()}
flush("table2_accuracy_shakespeare", t2s)
print("written", OUT)
