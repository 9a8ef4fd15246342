"""Finds a cell's parts by name: nothing here knows a cell, a deployment, a
traffic mix, a program or a metric in advance.

The benchmark's data lives under the first directory of ``paths`` in
``BENCHMARK.json`` (``fhebench/``); a later change adds a cell by adding
files there and entries to ``BENCHMARK.json``, and edits no file.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType


class SpecError(Exception):
    """``BENCHMARK.json`` or a file it names is missing or inconsistent."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<traffic>.json
    end_to_end: tuple     # BENCHMARK.json end_to_end entries this cell reports
    per_layer: tuple      # BENCHMARK.json per_layer entries this cell reports


def load_spec(root: Path) -> dict:
    path = Path(root) / "BENCHMARK.json"
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"no BENCHMARK.json at {root}") from None


def bench_dir(root: Path, spec: dict) -> Path:
    return Path(root) / spec["paths"][0]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing {path}") from None


def cell(root: Path, name: str) -> Cell:
    """The cell ``name`` with its configuration, traffic mix and metrics."""
    root = Path(root)
    spec = load_spec(root)
    found = [w for w in spec["workloads"] if w["name"] == name]
    if not found:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; one of "
                        f"{[w['name'] for w in spec['workloads']]}")
    w = found[0]
    cfgs = [c for c in spec["configs"] if c["name"] == w["config"]]
    if not cfgs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = _read_json(root / cfgs[0]["file"])
    traffic = _read_json(bench_dir(root, spec) / "traffic"
                         / f"{w['traffic']}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(m for m in spec["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in spec["per_layer"] if _reports(m, name)))


def _load_module(path: Path, qualname: str) -> ModuleType:
    if not path.is_file():
        raise SpecError(f"missing {path}")
    mod_spec = importlib.util.spec_from_file_location(qualname, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[qualname] = mod          # dataclasses look their module up
    mod_spec.loader.exec_module(mod)
    return mod


def program(root: Path, name: str) -> ModuleType:
    """``programs/<name>.py``: ``rotations(cfg)``, ``build(cfg, q, rng)``."""
    root = Path(root)
    return _load_module(bench_dir(root, load_spec(root)) / "programs"
                        / f"{name}.py", f"fhebench_program_{name}")


def driver(root: Path, name: str) -> ModuleType:
    """``drivers/<name>.py``: ``client_tenants(mix, cfg, tenants)`` and a
    ``Loop(engine, new_request, n_clients, harvest, annotate)`` with
    ``run_window``, ``drain`` and ``window_records``."""
    root = Path(root)
    return _load_module(bench_dir(root, load_spec(root)) / "drivers"
                        / f"{name}.py", f"fhebench_driver_{name}")


def metric_reader(root: Path, name: str) -> ModuleType:
    """The reader of the per-layer metric ``name``: ``metrics/<name>.py``,
    or for a name ``<base>.<suffix>`` with no file of its own,
    ``metrics/<base>.py`` (one reader serves a quantity split by the
    end-to-end metric it moves). A reader declares ``NEEDS_TRACE`` and
    ``read(window)``; its layer, unit, source and moved metric are those
    ``BENCHMARK.json`` gives the name."""
    metrics = bench_dir(Path(root), load_spec(root)) / "metrics"
    path = metrics / f"{name}.py"
    if not path.is_file() and "." in name:
        path = metrics / f"{name.split('.', 1)[0]}.py"
    return _load_module(path, "fhebench_metric_" + path.stem.replace(".", "_"))
