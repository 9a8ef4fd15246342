"""One run of one benchmark cell on the chip it finds.

    python3 fhebench/run.py --workload mrs48.batch --seed 7 --seconds 50 --trace 0

Builds the cell's deployment from ``--seed``, warms up the cell's own
shapes, serves the cell's traffic through ``FheServeEngine`` for
``--seconds`` (``--trace 1``: a profiled sub-window of at most 5 s),
decrypts every answer finished in the run and compares it with the float64
reference of its program.

Earlier stdout lines give the set-up breakdown and the window; the last line
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared for ``correct`` beside its limit, which also end stderr.

Exits 2, printing no result, when JAX finds no TPU, fewer chips than the
cell asks for, or a chip kind that ``peaks.json`` lacks; exits 1 when a
Pallas kernel ran in interpret mode or the run fails otherwise. The compile
cache is ``$JAX_COMPILATION_CACHE_DIR`` or ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()            # set-up runs from process start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
# launch configs are the committed defaults, never a tuned cache file
os.environ.pop("REPRO_AUTOTUNE_CACHE", None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from fhebench import cell
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program the run uses goes into the cache, however fast it
    # compiled, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        result, info = cell.run_cell(ROOT, args.workload, args.seed,
                                     args.seconds, bool(args.trace), T_START)
    except cell.NoChip as e:
        print(f"fhebench: {e}", file=sys.stderr)
        return 2
    except cell.RunFault as e:
        print(f"fhebench: {e}", file=sys.stderr)
        return 1
    dev = result["device"]
    tag = f"[{dev['platform']} {dev['kind']} x{dev['count']}]"
    print(f"setup {tag} {json.dumps(info['setup'])}")
    print(f"window {tag} {json.dumps(info['window'])}")
    print(json.dumps(result))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
