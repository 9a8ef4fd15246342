"""One traced run of a benchmark cell with the program's own spans on the
profiler clock: where the device's idle time and program time go, engine
phase by engine phase and HE op by HE op.

    python3 fhebench/span_run.py --workload mrs48.interactive --seed 7

Runs what ``run.py --trace 1`` runs and prints what it prints, with the
program's profiler sink (``repro.runtime.tracing.start(profiler=True)``) on
for the traced window alone. The result line's ``breakdown`` adds
``idle_by_span``, ``pairing`` and, where the launches pair,
``device_by_op`` and ``device_by_span`` (:mod:`fhebench.spans`); its
``metrics`` add ``dispatch_host_ms_per_req`` and ``idle_in_dispatch_share``
(suffixed ``.interactive`` in a cell that reports ``latency_p50_s``).
The window is ``cell.TRACE_SECONDS`` long, the longest that
``run.py --trace 1`` traces.

``cell.run_cell`` has no switch for a program's spans and deletes its trace;
this script wraps ``jax.profiler.start_trace`` and ``stop_trace`` for the
run instead: the sink goes on as the profiler starts (the window follows at
once) and off before it stops, which is before the drain. Exits as
``run.py`` does.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()            # set-up runs from process start

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from fhebench import spans  # noqa: E402


@contextlib.contextmanager
def program_spans():
    """While open, every ``jax.profiler`` trace also records the program's
    spans; yields a dict that gets ``"events"`` (:func:`spans.extract`) when
    the trace stops."""
    import jax
    from repro.runtime import tracing
    kept = {}
    start0, stop0 = jax.profiler.start_trace, jax.profiler.stop_trace

    def start_trace(log_dir, *args, **kwargs):
        start0(log_dir, *args, **kwargs)
        kept["dir"] = log_dir
        tracing.start(profiler=True)

    def stop_trace():
        tracing.stop()
        stop0()
        kept["events"] = spans.extract(kept["dir"])

    jax.profiler.start_trace, jax.profiler.stop_trace = start_trace, stop_trace
    try:
        yield kept
    finally:
        jax.profiler.start_trace, jax.profiler.stop_trace = start0, stop0


def add_spans(result: dict, events: dict, completed: int,
              suffix: str) -> dict:
    """The span breakdown and metrics, added to a ``--trace 1`` result."""
    st = spans.reduce(events)
    if st is None:
        return result
    bd = st.breakdown()
    result.setdefault("breakdown", {}).update(bd)
    pairing = bd["pairing"]
    if "device_by_op" not in bd:
        print(f"fhebench: device_by_op left out: {pairing['named']} of "
              f"{pairing['modules']} programs in the window pair with a "
              f"launch of their own name ({100 * pairing['share']:.2f}%, "
              f"under {100 * spans.MIN_PAIRED:.0f}%); names that differ: "
              f"{pairing['names_differ']}", file=sys.stderr)
    for name, value, unit in (
            ("dispatch_host_ms_per_req",
             spans.dispatch_host_ms_per_req(st, completed), "ms"),
            ("idle_in_dispatch_share", spans.idle_in_dispatch_share(st),
             "%")):
        if value is not None:
            result["metrics"][name + suffix] = {"value": value, "unit": unit}
    return result


def run(root: Path, workload: str, seed: int, seconds: float, t_start: float,
        require_tpu: bool = True) -> tuple:
    """``(result, info, events)`` of one traced run with spans."""
    from fhebench import cell, registry
    with program_spans() as kept:
        result, info = cell.run_cell(root, workload, seed, seconds, True,
                                     t_start, require_tpu=require_tpu)
    events = kept.get("events", {"devices": {}, "host": []})
    reports = {m["name"] for m in registry.cell(root, workload).end_to_end}
    suffix = ".interactive" if "latency_p50_s" in reports else ""
    add_spans(result, events, info["window"]["completed"], suffix)
    result["checks"] = result.pop("checks")      # last, as in run.py
    return result, info, events


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    # launch configs are the committed defaults, as in run.py
    os.environ.pop("REPRO_AUTOTUNE_CACHE", None)

    import jax

    from fhebench import cell
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()           # the compile cache as run.py keeps it
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        result, info, _ = run(ROOT, args.workload, args.seed,
                              cell.TRACE_SECONDS, T_START)
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
    return 0


if __name__ == "__main__":
    sys.exit(main())
