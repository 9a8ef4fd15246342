"""Observability benchmark: tracing overhead + determinism
(EXPERIMENTS.md §Observability).

Serves the same 8-request wave (2 tenants, the standard
multiply-rotate-accumulate program) through two warmed engines — one with
tracing OFF, one under ``tracing.capture()`` — interleaved so container
drift hits both equally.  The gates:

  * tracing OFF is genuinely zero-overhead: no launch/stage/fire hook
    installed, and the traced wave performs the IDENTICAL per-family kernel
    launches and const/evk uploads as the untraced one (tracing observes,
    never perturbs);
  * traced outputs are BIT-EXACT versus untraced (same seeds);
  * tracing ON costs ≤ 5% wall-clock (min-of-reps, interleaved);
  * the span-tree summary is byte-identical across two fresh seeded runs
    (no wall-clock leaks into it — CI can require exact equality);
  * the captured trace is valid Chrome/Perfetto trace-event JSON.

    PYTHONPATH=src python -m benchmarks.bench_obs [--quick] [--out PATH]
"""
import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, "src")

import numpy as np

from repro.core import const_cache
from repro.core import keys as K
from repro.core import params as prm
from repro.core import trace as he_trace
from repro.kernels import config as kconfig
from repro.runtime import faults, tracing
from repro.serve import (FheRequest, FheServeEngine, TenantKeyStore,
                         standard_request)

DEFAULT_OUT = Path(__file__).resolve().parent.parent / "BENCH_obs.json"

WAVE = 8
TENANTS = ("tenant0", "tenant1")


def _setup(N: int, L: int):
    p = prm.make_params(N=N, L=L, K=2, dnum=2)
    store = TenantKeyStore(max_resident=len(TENANTS))
    for i, t in enumerate(TENANTS):
        store.register(t, K.keygen(p, rotations=(1,), seed=i))
    return p, store


def _submit_wave(eng, p, store, base_seed: int) -> list[FheRequest]:
    reqs = []
    for i in range(WAVE):
        tenant = TENANTS[i % len(TENANTS)]
        req, _ = standard_request(p, store.keyset(tenant), tenant,
                                  base_seed + i)
        assert eng.submit(req)
        reqs.append(req)
    return reqs


def _ct_bits(ct):
    return (np.asarray(ct.a.to_ntt().data), np.asarray(ct.b.to_ntt().data))


def _timed_wave(eng, p, store, seed: int):
    """One steady-state wave: (seconds, per-family launches, uploads,
    output bits)."""
    reqs = _submit_wave(eng, p, store, seed)
    before_up = const_cache.stage_events()
    with kconfig.count_region() as c:
        t0 = time.perf_counter()
        eng.run_until_drained()
        dt = time.perf_counter() - t0
    bits = [_ct_bits(r.result()["out"]) for r in reqs]
    return dt, c.deltas, const_cache.stage_events_since(before_up), bits


def _perfetto_valid(doc) -> bool:
    events = doc.get("traceEvents")
    if not isinstance(events, list) or not events:
        return False
    for ev in events:
        if ev.get("ph") not in ("X", "i", "M"):
            return False
        if "name" not in ev or "pid" not in ev:
            return False
        if ev["ph"] == "X" and not ("ts" in ev and "dur" in ev):
            return False
        if ev["ph"] == "i" and "ts" not in ev:
            return False
    return True


def _traced_run(N: int, L: int, seed: int):
    """Fresh engine, warmed, then one wave captured with spans + OpTrace.
    Returns (span_summary, perfetto_doc, launches)."""
    p, store = _setup(N, L)
    eng = FheServeEngine(store, max_batch=WAVE)
    _submit_wave(eng, p, store, 0)
    eng.run_until_drained()                       # warm shapes/plans/keys
    with tracing.capture() as tr:
        with he_trace.trace_ops() as op_trace:
            _, launches, _, _ = _timed_wave(eng, p, store, seed)
    assert dict(op_trace.launches) == launches    # satellite-3 parity
    return tr.span_summary(), tr.to_perfetto(), launches


def run(reps: int, N: int, L: int) -> dict:
    assert not tracing.enabled(), "run bench_obs with REPRO_TRACE=off"
    p, store = _setup(N, L)
    eng_off = FheServeEngine(store, max_batch=WAVE)
    eng_on = FheServeEngine(store, max_batch=WAVE)
    for eng in (eng_off, eng_on):
        _submit_wave(eng, p, store, 0)
        eng.run_until_drained()

    hook_free = (kconfig.get_launch_hook() is None
                 and const_cache.get_stage_hook() is None
                 and faults.get_fire_hook() is None)

    times_off, times_on = [], []
    launches_off = launches_on = None
    uploads_off = uploads_on = None
    exact = True
    for rep in range(reps):
        seed = 1000 + rep
        dt, launches_off, uploads_off, bits_off = _timed_wave(
            eng_off, p, store, seed)
        times_off.append(dt)
        with tracing.capture():
            dt, launches_on, uploads_on, bits_on = _timed_wave(
                eng_on, p, store, seed)
        times_on.append(dt)
        if rep == 0:
            for (oa, ob), (na, nb) in zip(bits_off, bits_on):
                exact &= (np.array_equal(oa, na) and np.array_equal(ob, nb))
    hook_free &= (kconfig.get_launch_hook() is None
                  and const_cache.get_stage_hook() is None
                  and faults.get_fire_hook() is None)

    overhead_pct = 100.0 * (min(times_on) / min(times_off) - 1.0)

    # determinism on two fully fresh runs with the same seeds
    summ_a, doc, traced_launches = _traced_run(N, L, 2000)
    summ_b, _, _ = _traced_run(N, L, 2000)
    deterministic = summ_a == summ_b
    perfetto_ok = (_perfetto_valid(doc)
                   and json.loads(json.dumps(doc)) == doc)
    with tempfile.NamedTemporaryFile("w+", suffix=".json") as f:
        json.dump(doc, f)
        f.flush()
        f.seek(0)
        perfetto_ok &= _perfetto_valid(json.load(f))

    from benchmarks.bench_env import gate_env, run_env
    out = {
        "bench": "obs",
        "params": {"N": p.N, "L": p.L, "dnum": p.dnum,
                   "tenants": len(TENANTS), "wave": WAVE, "reps": reps},
        "env": run_env(),
        "wave_seconds_off": min(times_off),
        "wave_seconds_on": min(times_on),
        "overhead_pct": overhead_pct,
        "launches_off": launches_off,
        "launches_on": launches_on,
        "traced_launches": traced_launches,
        "span_summary": summ_a,
        "gate": {
            # booleans: invariants; numbers: must not grow vs baseline;
            # strings (mode/backend): must equal the baseline's
            **gate_env(),
            "trace_off_hook_free": bool(hook_free),
            "trace_off_zero_extra_launches": bool(
                launches_off == launches_on),
            "trace_off_zero_extra_uploads": bool(
                uploads_off == uploads_on),
            "traced_equals_untraced": bool(exact),
            "trace_overhead_within_5pct": bool(overhead_pct <= 5.0),
            "span_summary_deterministic": bool(deterministic),
            "perfetto_valid": bool(perfetto_ok),
            "traced_wave_spans": sum(v["count"]
                                     for v in summ_a["spans"].values()),
            "traced_wave_launches": sum(traced_launches.values()),
        },
    }
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="two timed reps (CI); default 3")
    ap.add_argument("--out", type=Path, default=DEFAULT_OUT)
    ap.add_argument("--N", type=int, default=1 << 10)
    ap.add_argument("--L", type=int, default=4)
    args = ap.parse_args(argv)
    res = run(reps=2 if args.quick else 3, N=args.N, L=args.L)
    args.out.write_text(json.dumps(res, indent=1, sort_keys=True) + "\n")
    print(json.dumps(res["gate"], indent=1))
    print(f"wrote {args.out}")
    failed = [k for k, v in res["gate"].items()
              if isinstance(v, bool) and v is not True]
    if failed:
        raise RuntimeError(f"obs gate invariants failed: {failed}")
    return res


if __name__ == "__main__":
    main()
