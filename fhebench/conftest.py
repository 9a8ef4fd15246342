"""Test fixtures: a checkout root holding a ``BENCHMARK.json`` of small
cells that the CPU can serve, with the benchmark's own files beside it."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent

# CPU-sized deployments: every algorithmic feature of the chip cells
# (hybrid key switching, double-prime rescale, fused rotations), at N=2^11
SMALL = {
    "std-small": {
        "N": 2048, "L": 8, "K": 2, "dnum": 4, "rescale_primes": 2,
        "program": "standard", "input_limbs": 8, "tenants": 2,
        "max_batch": 4, "pool_per_tenant": 2, "answer_limbs": 3,
        "limit_widest_gap": 1e-9},
}


def make_root(tmp: Path, configs: dict, workloads: list,
              per_layer: list = ()) -> Path:
    """A checkout root at ``tmp``: BENCHMARK.json naming ``configs`` and
    ``workloads`` (``(name, config, traffic)``), plus copies of the
    benchmark's traffic mixes, drivers, programs and metric readers."""
    bench = tmp / "fhebench"
    for sub in ("traffic", "drivers", "programs", "metrics"):
        shutil.copytree(BENCH / sub, bench / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs").mkdir()
    for name, cfg in configs.items():
        (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    spec = {
        "command": ["python3", "fhebench/run.py"], "paths": ["fhebench"],
        "run_seconds": 1,
        "configs": [{"name": n, "source": "test", "reduced": [], "why": "test",
                     "file": f"fhebench/configs/{n}.json"} for n in configs],
        "workloads": [{"name": w, "config": c, "traffic": t, "chips": 1,
                       "why": "test"} for w, c, t in workloads],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "req_per_s", "unit": "req/s", "better": "higher",
             "bound": 0.25, "source": "host_clock"},
            {"name": "latency_p50_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "latency_p95_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": list(per_layer),
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


@pytest.fixture
def small_root(tmp_path):
    return make_root(tmp_path, SMALL, [
        ("std.batch", "std-small", "batch"),
        ("std.interactive", "std-small", "interactive")])
