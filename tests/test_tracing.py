"""Tests for the structured tracing & profiling layer
(repro.runtime.tracing).

Covers the zero-overhead-when-off contract (no hooks installed, bit-exact
serve outputs), span nesting — including across the dispatch watchdog's
worker thread —, hook chaining with the fault injector, the OpTrace
kernel-launch parity, Perfetto export validity, histogram quantile /
merge / state behaviour, and the ServeMetrics latency-histogram round-trip
through crash-recovery engine state.
"""
import json
import os
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from repro.core import const_cache
from repro.core import keys as K
from repro.core import params as prm
from repro.core import trace as he_trace
from repro.kernels import config as kconfig
from repro.runtime import faults, tracing
from repro.serve import (FheServeEngine, TenantKeyStore, standard_request)
from repro.serve.metrics import ServeMetrics
from repro.serve.resilience import DispatchWatchdog

N, L = 1 << 9, 4
TENANTS = ("alice", "bob")
WAVE = 4


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    assert tracing.active_tracer() is None
    yield
    # a failing test must not leave a process-wide tracer (or its hooks)
    # behind for the rest of the suite
    if tracing.active_tracer() is not None:
        tracing.stop()


# ----------------------------------------------------------------------------
# spans + hooks (no kernels needed: count_launch IS the hook point)
# ----------------------------------------------------------------------------

def test_off_means_no_hooks_installed():
    assert not tracing.enabled()
    assert kconfig.get_launch_hook() is None
    assert const_cache.get_stage_hook() is None
    assert faults.get_fire_hook() is None
    # off: span() hands back one shared no-op object — nothing allocated
    assert tracing.span("x") is tracing.span("y")
    tracing.annotate("k")                 # all no-ops, no error
    tracing.event("e")
    tracing.request_event("admit", 0)


def test_span_nesting_paths_and_restore():
    with tracing.capture() as tr:
        assert kconfig.get_launch_hook() is not None
        with tracing.span("a", foo=1):
            with tracing.span("b"):
                kconfig.count_launch("ntt")
                tracing.annotate("ops", 3)
            with tracing.span("b"):
                pass
        with pytest.raises(RuntimeError):
            tracing.start()               # only one active tracer
    assert kconfig.get_launch_hook() is None
    assert const_cache.get_stage_hook() is None
    assert faults.get_fire_hook() is None
    paths = [s.path for s in tr.spans]    # completion order: inner first
    assert paths == [("a", "b"), ("a", "b"), ("a",)]
    summ = tr.span_summary()
    assert summ["spans"]["a/b"]["count"] == 2
    assert summ["spans"]["a/b"]["launches"] == {"ntt": 1}
    assert summ["spans"]["a/b"]["marks"] == {"ops": 3}
    assert summ["spans"]["a"]["launches"] == {}
    assert tr.launches == {"ntt": 1}


def _profiled(fn, tmp_path):
    """Run ``fn`` under a CPU ``jax.profiler`` trace; returns the host
    events named ``repro.*`` as (name, start_ns, end_ns, stats)."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    pb = sorted(Path(tmp_path).rglob("*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_file(str(pb))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(tracing.SPAN_PREFIX)]


def test_profiler_sink_writes_nested_spans(tmp_path):
    def body():
        with tracing.capture(profiler=True) as tr:
            assert tr is None
            # the sink alone installs no hook and records nothing in memory
            assert kconfig.get_launch_hook() is None
            assert const_cache.get_stage_hook() is None
            assert faults.get_fire_hook() is None
            assert tracing.active_tracer() is None
            with pytest.raises(RuntimeError):
                tracing.start()
            with tracing.span("step"):
                with tracing.span("dispatch.hrot", batch=2):
                    kconfig.count_launch("auto_ks")
    evs = _profiled(body, tmp_path)
    assert [n for n, *_ in evs] == ["repro.step", "repro.dispatch.hrot"]
    (_, s0, e0, _), (_, s1, e1, stats) = evs
    assert s0 <= s1 and e1 <= e0
    assert stats["batch"] == 2             # attributes stay off the name
    # off again: the shared no-op, and no hook
    assert tracing.span("x") is tracing.span("y")
    assert kconfig.get_launch_hook() is None


def test_profiler_sink_beside_an_in_memory_tracer(tmp_path):
    got = {}

    def body():
        with tracing.capture(tracing.Tracer(), profiler=True) as tr:
            assert kconfig.get_launch_hook() is not None
            with tracing.span("a"):
                with tracing.span("b"):
                    kconfig.count_launch("ntt")
        got["tr"] = tr
    evs = _profiled(body, tmp_path)
    assert [n for n, *_ in evs] == ["repro.a", "repro.b"]
    tr = got["tr"]
    assert [s.path for s in tr.spans] == [("a", "b"), ("a",)]
    assert tr.span_summary()["spans"]["a/b"]["launches"] == {"ntt": 1}
    assert kconfig.get_launch_hook() is None


def test_span_summary_has_no_wallclock():
    with tracing.capture() as tr:
        with tracing.span("s"):
            kconfig.count_launch("bconv", 2)
    blob = json.dumps(tr.span_summary(), sort_keys=True)
    # every value in the summary is a count/name — re-running the same
    # scripted sequence must reproduce it byte-for-byte
    with tracing.capture() as tr2:
        with tracing.span("s"):
            kconfig.count_launch("bconv", 2)
    assert blob == json.dumps(tr2.span_summary(), sort_keys=True)


def test_span_reentrancy_across_watchdog_worker_thread():
    wd = DispatchWatchdog(deadline=5.0)
    seen = {}
    with tracing.capture() as tr:
        with tracing.span("outer"):
            def fn():
                with tracing.span("inner") as s:
                    seen["path"] = s.path
                    seen["tid"] = threading.get_ident()
                    kconfig.count_launch("eltwise")
            wd.run(fn)
            # worker-side spans must not leak into the engine thread
            with tracing.span("after") as s:
                assert s.path == ("outer", "after")
    assert seen["path"] == ("outer", "inner")
    assert seen["tid"] != threading.get_ident()
    summ = tr.span_summary()
    assert summ["spans"]["outer/inner"]["launches"] == {"eltwise": 1}


def test_fire_hook_and_inject_chaining():
    plan = faults.FaultPlan.from_dict(
        {"seed": 0, "specs": [{"site": "launch", "rate": 1.0,
                               "max_fires": 1}]})
    with tracing.capture() as tr:
        with faults.inject(plan) as inj:
            with tracing.span("s"):
                with pytest.raises(faults.TransientFault):
                    kconfig.count_launch("ntt")       # injector fires first
                kconfig.count_launch("ntt")           # budget spent: retires
            assert inj.fired["launch"] == 1
        # inject exited: the tracer's own hook is back
        assert kconfig.get_launch_hook() is not None
        kconfig.count_launch("ntt")
    assert kconfig.get_launch_hook() is None
    # the faulted dispatch never retired — spans count only real launches
    assert tr.launches == {"ntt": 2}
    assert tr.span_summary()["spans"]["s"]["launches"] == {"ntt": 1}
    assert tr.fault_fires == {"launch": 1}
    assert [e[0] for e in tr.events] == ["fault.launch"]


def test_optrace_mirrors_count_region():
    with he_trace.trace_ops() as t:
        with kconfig.count_region() as c:
            kconfig.count_launch("ntt")
            kconfig.count_launch("ntt")
            kconfig.count_launch("bconv", 3)
    assert dict(t.launches) == c.deltas == {"ntt": 2, "bconv": 3}


def test_env_knob_rejects_unknown_mode():
    src = str(Path(__file__).resolve().parent.parent / "src")
    env = dict(os.environ, PYTHONPATH=src, REPRO_TRACE="bogus",
               JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-c", "import repro.runtime.tracing"],
        env=env, capture_output=True, text=True)
    assert r.returncode != 0
    assert "REPRO_TRACE" in r.stderr


def test_ring_and_ckks_layers_import_no_runtime():
    """Span call sites below the runtime import only the leaf
    ``repro.core.spans``; the runtime sets what a span opens."""
    src = str(Path(__file__).resolve().parent.parent / "src")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    code = ("import sys, repro.core.poly, repro.core.ckks; "
            "print(sorted(m for m in sys.modules "
            "if m.startswith('repro.runtime')))")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "[]"
    from repro.core import spans
    assert tracing.span is spans.span
    with tracing.capture():
        assert spans.opener() is not None
    assert spans.opener() is None and spans.span("x") is spans.NULL_SPAN


# ----------------------------------------------------------------------------
# histograms
# ----------------------------------------------------------------------------

def test_histogram_quantiles_within_bucket_error():
    h = tracing.Histogram()
    xs = np.linspace(0.001, 2.0, 500)
    for x in xs:
        h.observe(float(x))
    s = h.summary()
    assert s["count"] == 500
    assert s["min"] == pytest.approx(0.001) and s["max"] == pytest.approx(2.0)
    for q, key in ((50, "p50"), (95, "p95"), (99, "p99")):
        exact = float(np.percentile(xs, q))
        assert abs(s[key] - exact) / exact < 0.15, (key, s[key], exact)
    assert s["p50"] <= s["p95"] <= s["p99"] <= s["max"]


def test_histogram_under_overflow_and_empty():
    h = tracing.Histogram(lo=1e-3, hi=1e3)
    assert h.summary() == {"count": 0, "mean": 0.0, "min": 0.0, "max": 0.0,
                           "p50": 0.0, "p95": 0.0, "p99": 0.0}
    h.observe(1e-9)                       # underflow bucket
    h.observe(1e9)                        # overflow bucket
    s = h.summary()
    assert s["p50"] == 1e-9 and s["p99"] == 1e9     # exact observed extremes


def test_histogram_merge_and_state_roundtrip():
    a, b = tracing.Histogram(), tracing.Histogram()
    for x in (0.01, 0.02, 0.5):
        a.observe(x)
    for x in (1.0, 2.0):
        b.observe(x)
    a.merge(b)
    assert a.count == 5 and a.min == 0.01 and a.max == 2.0
    # state survives the recovery serdes (plain JSON, sort_keys)
    state = json.loads(json.dumps(a.state_dict(), sort_keys=True))
    c = tracing.Histogram.from_state(state)
    assert c.summary() == a.summary() and c.counts == a.counts
    with pytest.raises(ValueError):
        tracing.Histogram(lo=1e-2).load_state(state)  # bucket mismatch
    with pytest.raises(AssertionError):
        a.merge(tracing.Histogram(lo=1e-2))


def test_serve_metrics_histogram_state_excludes_dispatch():
    m = ServeMetrics()
    m.observe_wait(0.25)
    m.observe_serve(1.5)
    m.observe_dispatch(0.75)              # wall-clock: process-local
    lat = m.summary()["latency"]
    assert lat["wait"]["count"] == lat["serve"]["count"] == 1
    assert lat["dispatch"]["count"] == 1
    state = json.loads(json.dumps(m.state_dict(), sort_keys=True))
    assert set(state["histograms"]) == {"wait", "serve"}
    m2 = ServeMetrics()
    m2.load_state(state)
    assert m2.wait_hist.summary() == m.wait_hist.summary()
    assert m2.serve_hist.summary() == m.serve_hist.summary()
    assert m2.dispatch_hist.count == 0
    # pre-histogram snapshots (older journals) still load
    del state["histograms"]
    m3 = ServeMetrics()
    m3.load_state(state)
    assert m3.wait_hist.count == 0


# ----------------------------------------------------------------------------
# perfetto + snapshot/prometheus (synthetic tracer, no kernels)
# ----------------------------------------------------------------------------

def _synthetic_tracer():
    with tracing.capture() as tr:
        tracing.request_event("admit", 7, tenant="alice")
        with tracing.span("step"):
            tracing.request_event("start", 7)
            with tracing.span("dispatch.hmult", batch=2):
                kconfig.count_launch("bconv", 2)
                tracing.annotate("ops", 2)
            tracing.event("retry", attempt=1)
        tracing.request_event("terminal", 7, status="ok")
    return tr


def test_perfetto_export_schema(tmp_path):
    tr = _synthetic_tracer()
    path = tmp_path / "trace.json"
    tr.write_perfetto(path)
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert all(ev["ph"] in ("X", "i", "M") for ev in evs)
    for ev in evs:
        assert isinstance(ev["name"], str) and "pid" in ev and "tid" in ev
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0
        if ev["ph"] == "i":
            assert ev["s"] == "t" and ev["ts"] >= 0
    names = [ev["name"] for ev in evs]
    assert "fhe-serve engine" not in names          # metadata carries it
    assert {"step", "dispatch.hmult", "retry", "queued",
            "active:ok"} <= set(names)
    disp = next(ev for ev in evs if ev["name"] == "dispatch.hmult")
    assert disp["args"] == {"batch": 2, "launches": {"bconv": 2}, "ops": 2}
    req = [ev for ev in evs if ev["pid"] == 2 and ev["ph"] == "X"]
    assert all(ev["tid"] == 7 for ev in req)
    queued, active = (next(ev for ev in req if ev["name"] == n)
                      for n in ("queued", "active:ok"))
    assert queued["ts"] <= active["ts"]


def test_metrics_snapshot_and_prometheus_rendering():
    m = ServeMetrics()
    m.admitted = m.served = 3
    m.observe_serve(0.5)
    with tracing.capture():
        with tracing.span("s"):
            kconfig.count_launch("auto_ks")
        snap = tracing.metrics_snapshot(m)
        assert snap["trace"]["spans"] == 1
        assert snap["trace"]["launches"] == {"auto_ks": 1}
    assert snap["kernel_launches"]["auto_ks"] >= 1
    assert snap["serve"]["served"] == 3
    assert snap["histograms"]["serve"]["count"] == 1
    text = tracing.render_prometheus(snap)
    assert '# TYPE repro_kernel_launches_total counter' in text
    assert 'repro_kernel_launches_total{family="auto_ks"}' in text
    assert "repro_serve_served 3" in text
    assert 'repro_serve_serve_seconds{quantile="0.99"}' in text
    assert "repro_serve_serve_seconds_count 1" in text


# ----------------------------------------------------------------------------
# serve integration: traced waves (module fixture shares compiled shapes)
# ----------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_setup():
    p = prm.make_params(N=N, L=L, K=2, dnum=2)
    store = TenantKeyStore(max_resident=len(TENANTS))
    for i, t in enumerate(TENANTS):
        store.register(t, K.keygen(p, rotations=(1,), seed=i))
    eng = FheServeEngine(store, max_batch=WAVE)
    _wave(eng, p, store, 0)               # warm: compile + stage + plans
    eng.run_until_drained()
    return p, store, eng


def _wave(eng, p, store, base_seed):
    reqs = []
    for i in range(WAVE):
        t = TENANTS[i % len(TENANTS)]
        req, _ = standard_request(p, store.keyset(t), t, base_seed + i)
        assert eng.submit(req)
        reqs.append(req)
    return reqs


def _bits(req):
    out = req.result()["out"]
    return (np.asarray(out.a.to_ntt().data), np.asarray(out.b.to_ntt().data))


def test_traced_wave_timeline_and_determinism(serve_setup):
    p, store, eng = serve_setup
    with tracing.capture() as tr:
        reqs = _wave(eng, p, store, 100)
        eng.run_until_drained()
    summ = tr.span_summary()
    assert summ["requests"]["admitted"] == WAVE
    assert summ["requests"]["started"] == WAVE
    assert summ["requests"]["terminal"] == {"ok": WAVE}
    assert summ["spans"]["admit"]["count"] == WAVE
    # the program is hmult → rescale → hrot → hadd: every family shows up
    # under a step/dispatch.* span, launches attributed to the dispatch
    dispatch_paths = [k for k in summ["spans"] if "/dispatch." in k]
    assert dispatch_paths and all(k.startswith("step/")
                                  for k in dispatch_paths)
    attributed = sum(n for k in dispatch_paths
                     for n in summ["spans"][k]["launches"].values())
    assert attributed == sum(tr.launches.values()) > 0
    assert all(r.status == "ok" for r in reqs)
    # same seeds on the warm engine: the span tree reproduces exactly
    with tracing.capture() as tr2:
        _wave(eng, p, store, 100)
        eng.run_until_drained()
    assert json.dumps(summ, sort_keys=True) == \
        json.dumps(tr2.span_summary(), sort_keys=True)


def test_engine_spans_under_the_profiler_sink(serve_setup, tmp_path):
    p, store, eng = serve_setup

    def body():
        with tracing.capture(profiler=True):
            _wave(eng, p, store, 300)
            eng.run_until_drained()
    evs = _profiled(body, tmp_path)
    by = {}
    for n, s, e, _ in evs:
        by.setdefault(n[len(tracing.SPAN_PREFIX):], []).append((s, e))

    def inside(name, outer):
        return by[name] and all(any(a <= s and e <= b for o in outer
                                    for a, b in by.get(o, ()))
                                for s, e in by[name])
    kinds = [n for n in by if n.startswith("dispatch.")]
    assert {"dispatch.hmult", "dispatch.rescale", "dispatch.hrot",
            "dispatch.hadd"} <= set(kinds)
    for name in kinds + ["fill", "retire", "outputs"]:
        assert inside(name, ["step"]), name
    assert inside("stage", ["fill"])
    for name in ("plan", "scatter", "modup", "moddown", "rescale",
                 "auto_ks"):
        assert inside(name, kinds), name
    # encryption of the wave's inputs transforms outside any step; inside
    # one, every transform belongs to a dispatch
    by["ntt"] = [(s, e) for s, e in by["ntt"]
                 if any(a <= s and e <= b for a, b in by["step"])]
    assert inside("ntt", ["modup", "moddown", "rescale"] + kinds)
    assert inside("inner", ["dispatch.hmult"])


def test_tracing_off_is_bit_exact(serve_setup):
    p, store, eng = serve_setup
    off_reqs = _wave(eng, p, store, 200)
    eng.run_until_drained()
    off = [_bits(r) for r in off_reqs]
    with tracing.capture():
        on_reqs = _wave(eng, p, store, 200)
        eng.run_until_drained()
    on = [_bits(r) for r in on_reqs]
    for (oa, ob), (na, nb) in zip(off, on):
        assert np.array_equal(oa, na) and np.array_equal(ob, nb)
    assert kconfig.get_launch_hook() is None


def test_metrics_histograms_roundtrip_engine_state(serve_setup):
    p, store, eng = serve_setup
    from repro.serve import recovery
    state = json.loads(json.dumps(recovery.engine_state(eng),
                                  sort_keys=True))
    wait = state["metrics"]["histograms"]["wait"]
    assert wait["count"] == eng.metrics.wait_hist.count > 0
    eng2 = FheServeEngine(store, max_batch=WAVE)
    recovery.load_engine_state(eng2, state, restage=False)
    assert eng2.metrics.wait_hist.summary() == \
        eng.metrics.wait_hist.summary()
    assert eng2.metrics.serve_hist.summary() == \
        eng.metrics.serve_hist.summary()
