"""A later change adds a cell by adding files only: a configuration, a
program and a per-layer metric dropped into a checkout are found by the
names ``BENCHMARK.json`` gives them, and the run reports the new metric."""
import json
import time

import pytest

from fhebench import cell, registry
from fhebench.conftest import SMALL, make_root

PROGRAM = '''
from fhebench.reference import Instance, Operand, ScaleTracker


def rotations(cfg):
    return ()


def build(cfg, q, rng):
    t = ScaleTracker(q, cfg["rescale_primes"])
    limbs = cfg["input_limbs"]
    x = rng.uniform(-1, 1, cfg["N"] // 2)
    t.input("x", limbs, t.top_primes(limbs))
    t.add("out", "x", "x")
    return Instance(t.ops, {"x": Operand(x, t.scale("x"), limbs)}, {},
                    ("out",))
'''

METRIC = '''
NEEDS_TRACE = False


def read(win):
    return win.counters["steps"] / win.completed
'''


def test_added_files_are_found_and_run(tmp_path):
    cfg = dict(SMALL["std-small"], program="twice")
    entry = {"name": "steps_per_req", "unit": "steps", "better": "lower",
             "source": "program_counter", "layer": "driver",
             "moves": "req_per_s", "workloads": ["twice.batch"]}
    root = make_root(tmp_path, {"twice-small": cfg},
                     [("twice.batch", "twice-small", "batch")], [entry])
    (root / "fhebench" / "programs" / "twice.py").write_text(PROGRAM)
    (root / "fhebench" / "metrics" / "steps_per_req.py").write_text(METRIC)
    c = registry.cell(root, "twice.batch")
    assert c.config["program"] == "twice" and c.per_layer == (entry,)
    result, _ = cell.run_cell(root, "twice.batch", 3, 0.3, True,
                              time.perf_counter(), require_tpu=False)
    assert result["correct"], result["checks"]
    # one op per request: in lockstep, one engine step per completed wave
    # of max_batch requests
    assert result["metrics"]["steps_per_req"]["value"] == \
        pytest.approx(1 / cfg["max_batch"])


def test_a_suffixed_metric_uses_its_base_reader(tmp_path):
    """``<base>.<suffix>`` without a file of its own is read by
    ``metrics/<base>.py``, with the unit and name the entry gives it."""
    entry = {"name": "steps_per_req.interactive", "unit": "steps",
             "better": "lower", "source": "program_counter",
             "layer": "driver", "moves": "latency_p50_s",
             "workloads": ["twice.interactive"]}
    cfg = dict(SMALL["std-small"], program="twice")
    root = make_root(tmp_path, {"twice-small": cfg},
                     [("twice.interactive", "twice-small", "interactive")],
                     [entry])
    (root / "fhebench" / "programs" / "twice.py").write_text(PROGRAM)
    (root / "fhebench" / "metrics" / "steps_per_req.py").write_text(METRIC)
    assert registry.metric_reader(root, entry["name"]).NEEDS_TRACE is False
    result, _ = cell.run_cell(root, "twice.interactive", 5, 0.3, True,
                              time.perf_counter(), require_tpu=False)
    assert result["correct"], result["checks"]
    # one op per request, one client per tenant: one step per wave of
    # ``tenants`` requests
    got = result["metrics"]["steps_per_req.interactive"]
    assert got == {"value": pytest.approx(1 / cfg["tenants"]),
                   "unit": "steps"}
    with pytest.raises(registry.SpecError, match="missing"):
        registry.metric_reader(root, "nope.interactive")


def test_unknown_names_are_errors(small_root):
    with pytest.raises(registry.SpecError, match="no workload"):
        registry.cell(small_root, "nope.batch")
    spec = json.loads((small_root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "x.batch", "config": "std-small",
                              "traffic": "nope", "chips": 1, "why": "t"})
    (small_root / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(registry.SpecError, match="missing"):
        registry.cell(small_root, "x.batch")
