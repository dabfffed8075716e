"""Shared set-up of the benchmark's CPU tests: the harness modules on the
path, no persistent compile cache, and cells cut to a size a test holds."""
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "bench", ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

# a test run writes no persistent compile cache into the checkout
jax.config.update("jax_enable_compilation_cache", False)

TINY = {
    "cohort": dict(n_clients=6, straggler_ids={"first": 1}, samples_per_client=100,
                       local_epochs=1, warm_sub_model_counts=[1, 3]),
    "population": dict(n_clients=2000, cohort_size=32, n_partitions=8,
                       samples_per_partition=30, warm_sub_model_counts=[0, 2]),
}


def tiny_cell(name: str, traffic: str = None) -> dict:
    """The cell as BENCHMARK.json defines it, with its traffic cut down;
    `traffic` swaps in another mix of bench/traffic/ (one no cell uses
    yet, such as the population mix)."""
    import json
    import run
    cell = run.load_cell(name)
    if traffic is not None:
        cell["traffic"] = json.loads(
            (ROOT / "bench" / "traffic" / f"{traffic}.json").read_text())
    cell["traffic"].update(TINY[cell["traffic"]["entry"]])
    return cell


@pytest.fixture
def quiet_log():
    return lambda *a, **k: None
