"""Readings that a cell's answer limit is set from, for many seeds in one
process (set-up is long, so one process serves them all):

    python3 fhebench/readings.py --workload mrs48.batch --seconds 50 \
        --seeds 11,12,13

For each seed, one run of the cell as ``run.py`` makes it, with the control
(``run_cell(control=True)``): after the window, the program's reference
evaluated in float32 is encrypted at each answer's scale and primes and put
in the place of the program's answers, and the run's own check judges it.
Printed per seed: the control's result line, then the program's widest gap
and the control's from the same window. The benchmark's own runs do not
run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
os.environ.pop("REPRO_AUTOTUNE_CACHE", None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated integers")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    from fhebench import cell
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        result, info = cell.run_cell(ROOT, args.workload, seed, args.seconds,
                                     False, t0, control=True)
        print(json.dumps(result), flush=True)
        program = info["program_checks"]
        print(json.dumps({
            "seed": seed, "correct": info["program_correct"],
            "widest_gap": program["widest_gap"]["value"],
            "control_gap": result["checks"]["widest_gap"]["value"],
            "control_correct": result["correct"],
            "limit": result["checks"]["widest_gap"]["limit"],
            "answers": result["checks"]["answers_checked"]["value"],
            "setup": info["setup"], "window": info["window"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
