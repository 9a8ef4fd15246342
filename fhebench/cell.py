"""One run of one cell: set-up from the seed, warm-up of the cell's own
shapes, the measured window, the answer check, and the result."""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import statistics
import tempfile
import time
from pathlib import Path

import numpy as np

from fhebench import BENCH_DIR, answers, deploy, registry, stats

TRACE_SECONDS = 5.0              # length of the profiled sub-window
GIVE_UP_S = 240.0                # a window or drain with no completion
WARMUP_GIVE_UP_S = 1100.0        # the warm-up wave, which may compile


class NoChip(Exception):
    """JAX finds no TPU, fewer chips than the cell asks for, or a chip kind
    that the peaks table does not know."""


class RunFault(Exception):
    """The run itself went wrong (a Pallas launch ran in interpret mode)."""


def peaks(kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in peaks.json "
                     f"({sorted(table)})")
    return table[kind]


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu:
        if info["platform"] != "tpu":
            raise NoChip(f"no TPU: JAX runs on {info['platform']!r}")
        if info["count"] < chips:
            raise NoChip(f"cell asks for {chips} chips, JAX finds "
                         f"{info['count']}")
        peaks(info["kind"])
    return info


class CompileCounter:
    """Counts jaxpr traces, lowerings, XLA compilations and persistent-cache
    reads and writes through ``jax.monitoring``."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        self.cache_writes = 0
        self.traces = 0
        self.lowerings = 0

    def on_duration(self, event, duration, **kw):
        # fires for every program built, whether compiled or cache-loaded
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowerings += 1

    def on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self.on_duration)
        monitoring.register_event_listener(self.on_event)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring as mon
        mon.unregister_event_duration_listener(self.on_duration)
        mon.unregister_event_listener(self.on_event)
        return False

    def snapshot(self) -> tuple:
        return (self.compiles, self.cache_hits, self.cache_writes,
                self.traces, self.lowerings)


class GcTimer:
    """Seconds the host spent in Python's garbage collector while
    ``on``."""

    def __init__(self):
        self.on = False
        self.seconds = 0.0
        self.collections = 0
        self._t = 0.0

    def __call__(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t
            self.collections += 1

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)
        return False


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(f"fhebench.{name}")


@dataclasses.dataclass
class Window:
    """What the metric readers see of one measured window."""
    cell: registry.Cell
    deployment: deploy.Deployment
    seconds: float               # window length on the host clock
    completed: int               # requests completed inside the window
    latencies: list              # seconds, every request completed inside
    counters: dict               # ServeMetrics deltas over the window
    plan_uses: dict              # PlanCache key → dispatches in the window
    trace: object = None         # trace_reduce.DeviceTrace (--trace 1)
    peaks: dict | None = None    # peaks.json entry of the chip


def _plan_uses(eng) -> dict:
    return {k: eng.plans._plans[k].uses for k in eng.plans.keys()}


COUNTERS = ("ops_executed", "groups_dispatched", "steps", "served")


def stalls(phases: list, n: int = 3) -> dict:
    """Where the window's host time went: the median ``step``, and the
    ``n`` slowest phases as ``[phase, seconds from the window's start,
    seconds]``."""
    steps = [d for name, _, d in phases if name == "step"]
    slow = sorted(phases, key=lambda p: -p[2])[:n]
    return {"step_s_median": round(statistics.median(steps), 4)
            if steps else None,
            "slowest": [[name, round(at, 3), round(d, 4)]
                        for name, at, d in slow]}


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, require_tpu: bool = True,
             control: bool = False) -> tuple:
    """Everything of one run; returns ``(result, info)``: the result line's
    object (see run.py) and the set-up and window breakdown.

    ``control``: after the window, the control (:func:`answers.
    control_answers`) takes the place of the program's answers, and the
    result is the control's; ``info["program_checks"]`` keeps the
    program's own checks of the same window."""
    import jax

    from repro.kernels import config as kconfig
    from repro.serve import FheServeEngine

    cell = registry.cell(root, workload)
    cfg, mix = cell.config, cell.traffic
    device = device_info(cell.chips, require_tpu)
    chip_peaks = peaks(device["kind"]) if require_tpu else None
    program = registry.program(root, cfg["program"])
    driver = registry.driver(root, mix["driver"])

    with CompileCounter() as cc, GcTimer() as gct:
        dep = deploy.build(cfg, program, seed)
        eng = FheServeEngine(dep.store, max_batch=cfg["max_batch"])
        tenants = driver.client_tenants(mix, cfg, dep.tenants)
        per_tenant = {t: [i for i, e in enumerate(dep.pool) if e.tenant == t]
                      for t in dep.tenants}
        pick = np.random.default_rng(deploy.seed_int(seed, 3))
        entry_of = {}

        def new_request(client):
            choices = per_tenant[tenants[client]]
            e = choices[int(pick.integers(len(choices)))]
            req = dep.request(dep.pool[e])
            entry_of[req.rid] = e
            return req

        # warm-up: one wave of the cell's own traffic, answers kept as in
        # the window, compiles (or loads from the persistent cache) every
        # program the window runs
        t0 = time.perf_counter()
        warm = driver.Loop(eng, new_request, len(tenants),
                           harvest=answers.Harvest(cfg["answer_limbs"],
                                                   entry_of),
                           annotate=annotate)
        warm.run_window(0.0, WARMUP_GIVE_UP_S)
        warm.drain(GIVE_UP_S)
        dep.timings["warmup_s"] = time.perf_counter() - t0
        setup_compiles = cc.snapshot()

        m0 = dataclasses.asdict(eng.metrics)
        uses0 = _plan_uses(eng)
        harvest = answers.Harvest(cfg["answer_limbs"], entry_of)
        loop = driver.Loop(eng, new_request, len(tenants), harvest=harvest,
                           annotate=annotate)
        length = min(seconds, TRACE_SECONDS) if trace else seconds
        trace_dir = tempfile.mkdtemp(prefix="fhebench_trace_") \
            if trace else None
        setup_s = time.perf_counter() - t_start
        if trace:
            jax.profiler.start_trace(trace_dir)
        gct.on = True
        try:
            with annotate("window"):
                loop.run_window(length, GIVE_UP_S)
        finally:
            gct.on = False
            if trace:
                jax.profiler.stop_trace()
        window_builds = [b - a for a, b in zip(setup_compiles,
                                               cc.snapshot())]
        m1 = dataclasses.asdict(eng.metrics)
        uses1 = _plan_uses(eng)
        loop.drain(GIVE_UP_S)

    mem = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    modes = kconfig.mode_launch_counts()
    if require_tpu and modes["interpret"]:
        raise RunFault(f"Pallas launches ran in interpret mode: "
                       f"{modes['interpret']}")

    done_in = [r for r in loop.window_records() if r.status == "ok"]
    win = Window(
        cell=cell, deployment=dep, seconds=loop.t_close - loop.t0,
        completed=len(done_in),
        latencies=[r.done - r.submitted for r in done_in],
        counters={k: m1[k] - m0[k] for k in COUNTERS},
        plan_uses={k: uses1[k] - uses0.get(k, 0) for k in uses1
                   if uses1[k] - uses0.get(k, 0)},
        peaks=chip_peaks)

    # the engine's state goes before the reference runs
    loop.engine = warm.engine = None
    del eng
    sks = {t: dep.store.keyset(t).sk for t in dep.tenants}
    instances = [e.inst for e in dep.pool]
    refs = answers.reference_outputs(instances)
    records = warm.records + loop.records
    failed = [r for r in records if r.status != "ok"]
    n_out = len(dep.pool[0].inst.outputs)
    due = n_out * sum(r.status == "ok" for r in loop.records)
    limit = float(cfg["limit_widest_gap"])

    def judge(got: list) -> tuple:
        residues = answers.decrypt_limbs(got, sks, dep.params.N)
        gap = answers.widest_gap(got, residues, refs)
        checks = {
            "widest_gap": {"value": gap, "limit": limit},
            "failed_requests": {"value": len(failed), "limit": 0},
            "answers_checked": {"value": len(got), "limit": due},
        }
        return checks, (gap < limit and not failed and due > 0
                        and len(got) == due)

    t0 = time.perf_counter()
    checks, correct = judge(harvest.answers)
    check_s = time.perf_counter() - t0
    program_checks, program_correct = checks, correct
    if control:
        checks, correct = judge(answers.control_answers(
            harvest.answers, instances, sks, dep.params.N,
            deploy.seed_int(seed, 4)))

    info = {
        "setup": {**{k: round(v, 3) for k, v in dep.timings.items()},
                  "setup_s": round(setup_s, 3),
                  "programs_built": setup_compiles[0],
                  "loaded_from_cache": setup_compiles[1],
                  "compiled": setup_compiles[0] - setup_compiles[1],
                  "written_to_cache": setup_compiles[2]},
        "window": {"seconds": round(win.seconds, 3),
                   "completed": win.completed, "steps": loop.steps,
                   "programs_built": window_builds[0],
                   "traced": window_builds[3],
                   "lowered": window_builds[4],
                   "gc_s": round(gct.seconds, 4),
                   "gc_collections": gct.collections,
                   **stalls(loop.phases),
                   "check_s": round(check_s, 3)},
    }
    if control:
        info["program_checks"] = program_checks
        info["program_correct"] = program_correct
    result = {"correct": bool(correct), "attempted": len(records),
              "failed": len(failed), "metrics": {},
              "device": {**device, "memory_peak_bytes": mem}}
    if trace:
        from fhebench import trace_reduce
        try:
            win.trace = trace_reduce.load(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if win.trace is not None:
            result["device"]["busy_s"] = win.trace.busy_s
            result["device"]["window_s"] = win.trace.window_s
            result["breakdown"] = win.trace.breakdown()
        for entry in cell.per_layer:
            reader = registry.metric_reader(root, entry["name"])
            if reader.NEEDS_TRACE and win.trace is None:
                continue
            value = reader.read(win)
            if value is not None:
                result["metrics"][entry["name"]] = {"value": value,
                                                    "unit": entry["unit"]}
    else:
        for entry in cell.end_to_end:
            result["metrics"][entry["name"]] = {
                "value": end_to_end(entry["name"], setup_s, win),
                "unit": entry["unit"]}
    result["checks"] = checks
    return result, info


def end_to_end(name: str, setup_s: float, win: Window) -> float:
    if name == "setup_s":
        return setup_s
    if name == "req_per_s":
        return win.completed / win.seconds
    if name == "latency_p50_s":
        return stats.percentile(win.latencies, 50)
    if name == "latency_p95_s":
        return stats.percentile(win.latencies, 95)
    raise registry.SpecError(f"no end-to-end metric {name!r}")
