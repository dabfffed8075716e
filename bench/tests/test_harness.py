"""The harness's refusals, and that every cell resolves from files alone."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT

BM = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BM["workloads"]]


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    """On the CPU: a non-zero exit and no result line."""
    p = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_without_program(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in BM["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_device_kind_refused(monkeypatch):
    import run

    class Dev:
        platform, device_kind = "tpu", "TPU v0 imaginary"
    monkeypatch.setattr("jax.devices", lambda: [Dev()])
    with pytest.raises(run.EnvironmentRefused, match="peaks.json"):
        run.check_devices(1, require_tpu=True)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_from_files(cell):
    """A cell is its entries in BENCHMARK.json plus files found by name: the
    configuration, its model family, the traffic mix, the limits and one
    reader per metric it reports."""
    import importlib
    import run
    spec = run.load_cell(cell)
    assert spec["traffic"]["fixed_rate"] < 1.0
    assert set(spec["limits"]) == {"loss_gap", "update_gap", "change_gap",
                                   "plan_mismatch"}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(importlib.import_module(
            f"bench.metrics.{m['name']}").read)
    assert any(m["name"] == "setup_s" for m in spec["end_to_end"])
    assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]
