"""Aggregate benchmark runner: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--skip-hlo] [--skip-measured]
"""
import argparse
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, "src")


def _tmp(name: str) -> str:
    """A candidate output under the temp dir ($TMPDIR-aware)."""
    return str(Path(tempfile.gettempdir()) / name)


def _autotune_sweep() -> None:
    """Quick launch-config sweep, kept in memory: the later sections run on
    the committed defaults (or $REPRO_AUTOTUNE_CACHE), and no file is
    written."""
    from repro.kernels import autotune
    try:
        autotune.sweep(Ns=(1024,), ells=(4,), reps=3, persist=False,
                       max_candidates=6)
    finally:
        autotune.set_cache_path(None)    # drop the in-memory winners


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-hlo", action="store_true",
                    help="skip the subprocess HLO traffic benchmark")
    ap.add_argument("--skip-measured", action="store_true",
                    help="skip wall-clock micro-benchmarks")
    args = ap.parse_args()

    from benchmarks import (bench_area, bench_ks_traffic, bench_limbdup,
                            bench_mapping, bench_scaling, bench_workloads,
                            roofline)

    sections = [
        ("Table II (area)", bench_area.main),
        ("Table III (workloads)", bench_workloads.main),
        ("Fig. 4 (KS traffic vs ell)", bench_ks_traffic.main),
        ("Fig. 6 (mapping sweep)", bench_mapping.main),
        ("Fig. 7/8 (limb duplication)", bench_limbdup.main),
        ("Fig. 9 (scaling)", bench_scaling.main),
        ("Roofline (dry-run cells)", roofline.main),
    ]
    if not args.skip_hlo:
        from benchmarks import bench_limbdup_hlo
        sections.append(("Fig. 7 from compiled HLO", bench_limbdup_hlo.main))
    if not args.skip_measured:
        from benchmarks import bench_chaos, bench_ntt, bench_serve
        # machine-readable BENCH_*.json candidates go to the temp dir — the committed
        # repo-root baselines are the CI comparison targets and must only be
        # refreshed deliberately (full-rep runs, see README)
        sections.append(("Kernel autotune sweep (launch configs)",
                         _autotune_sweep))
        sections.append(("NTT micro-bench (measured)",
                         lambda: bench_ntt.main(
                             ["--quick", "--out", _tmp("BENCH_ntt.json")])))
        sections.append(("FHE serving throughput (measured)",
                         lambda: bench_serve.main(
                             ["--quick", "--out", _tmp("BENCH_serve.json")])))
        sections.append(("FHE serving under fault injection (chaos)",
                         lambda: bench_chaos.main(
                             ["--quick", "--out", _tmp("BENCH_chaos.json")])))
        from benchmarks import bench_recovery
        sections.append(("Crash-safe serving: recovery + watchdog gates",
                         lambda: bench_recovery.main(
                             ["--quick", "--out",
                              _tmp("BENCH_recovery.json")])))
        from benchmarks import bench_obs
        sections.append(("Observability: tracing overhead + determinism",
                         lambda: bench_obs.main(
                             ["--quick", "--out", _tmp("BENCH_obs.json")])))
        from benchmarks import bench_distributed
        sections.append(("Distributed scaling (1-8 chips, measured)",
                         lambda: bench_distributed.main(
                             ["--quick", "--out",
                              _tmp("BENCH_distributed.json")])))

    for title, fn in sections:
        print(f"\n### {title}")
        t0 = time.time()
        try:
            fn()
        except Exception as e:  # keep the sweep alive; report the failure
            print(f"BENCH-ERROR {title}: {type(e).__name__}: {e}")
        print(f"### done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
