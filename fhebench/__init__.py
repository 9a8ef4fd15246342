"""On-chip benchmark of the FHE serving engine.

One run serves one cell of ``BENCHMARK.json`` (a deployment under a traffic
mix) through ``FheServeEngine`` for a fixed window and prints one JSON line:

    python3 fhebench/run.py --workload mrs48.batch --seed 7 --seconds 50 --trace 0

Everything that belongs to one deployment, traffic mix, HE program or
per-layer metric is a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``configs/<config>.json``   — parameter set, tenants, keys, program, batch;
* ``traffic/<traffic>.json``  — a traffic mix: parameters of a driver;
* ``drivers/<driver>.py``     — a traffic driver (``closed`` today);
* ``programs/<program>.py``   — an HE program, its inputs and constants;
* ``metrics/<metric>.py``     — one per-layer metric reader.

The yardstick lives here and not in the program: the float64 reference
(:mod:`fhebench.answers`), the trace reduction (:mod:`fhebench.trace_reduce`),
the least-bytes functions (:mod:`fhebench.work_bytes`), the chip peaks
(``peaks.json``) and the percentile arithmetic (:mod:`fhebench.stats`).
"""
from __future__ import annotations

import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
_SRC = str(ROOT / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
