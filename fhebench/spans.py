"""The program's own spans in a profiler trace: each device idle gap blamed on
the engine phase the host was in, and each device program on the HE op that
launched it.

``repro.runtime.tracing``'s profiler sink (``tracing.start(profiler=True)``)
writes every span as a host event ``repro.<name>`` on the thread that runs
the engine, on the clock of the device's events. Every jitted call leaves
host events on the same thread: ``PjitFunction(<fn>)`` around the launch and,
inside it, the backend's ``…Executable::Execute``; the device runs the
program as ``jit_<fn>`` on its ``XLA Modules`` line. From these, over the
window that :mod:`fhebench.trace_reduce` reduces:

* ``idle_by_span``: every idle gap of the window split exactly, by interval
  intersection, by the innermost span open on the engine thread: a
  ``repro.*`` span by its path (``step/dispatch.hrot/modup``), else the
  benchmark's phase (``fhebench.step``), else ``other``;
* ``device_by_op``: device seconds of the window's programs by the
  ``repro.dispatch.<kind>`` span their launch was made in (``dispatch.hrot``;
  a launch outside every dispatch keeps its innermost label). Launches pair
  with programs in order and only where their names agree; when fewer than
  ``MIN_PAIRED`` of the window's programs pair, it is left out and
  ``pairing`` says why.

The spans of other threads are not read: the engine runs on the thread that
holds the ``fhebench.window`` annotation.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import re
from pathlib import Path

from fhebench import trace_reduce

SPAN_PREFIX = "repro."
DISPATCH = "dispatch."
PJIT = re.compile(r"^PjitFunction\((.*)\)$")
# the backend's launch: PjRtCpuExecutable::Execute on the CPU, the PJRT
# C API's "PJRT_LoadedExecutable_Execute linkage" on a TPU
EXECUTE = re.compile(r"Executable::Execute$|^PJRT_LoadedExecutable_Execute\b")
MIN_PAIRED = 0.99
MAX_SHIFT = 64           # programs launched before or run after the trace
LEAD_QUANTILE = 0.01     # of program start − launch, over the named pairs


def _name_key(name: str) -> str:
    """``jit__take`` and ``jit_<_take>`` alike: letters and digits only."""
    return re.sub(r"[^A-Za-z0-9]", "", name)


def _engine_thread(plane):
    for line in plane.lines:
        for e in line.events:
            if e.name == trace_reduce.WINDOW:
                return line
    return None


def extract(trace_dir) -> dict:
    """:func:`trace_reduce.extract`'s events, plus the engine thread's
    ``"spans"`` and ``"launches"`` (:func:`engine_events`)."""
    import jax
    events = trace_reduce.extract(trace_dir)
    events["spans"], events["launches"] = [], []
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        return events
    pd = jax.profiler.ProfileData.from_file(str(files[-1]))
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        line = _engine_thread(plane)
        if line is not None:
            events["spans"], events["launches"] = engine_events(
                (e.name, e.start_ns, e.duration_ns) for e in line.events)
            break
    return events


def engine_events(events) -> tuple:
    """From the engine thread's ``(name, start_ns, dur_ns)`` events:
    ``spans`` (``repro.*`` and ``fhebench.*``, ``[name, start_ns, dur_ns]``)
    and ``launches`` (``[fn, start_ns]``: one per backend ``Execute``, named
    by the outermost ``PjitFunction(fn)`` around it, at the ``Execute``'s
    start; ``""`` outside any)."""
    spans, launches = [], []
    outer_end, outer_fn = float("-inf"), ""   # outermost PjitFunction
    exec_end = float("-inf")                  # last launch kept
    for s, d, name in sorted((s, -d, n) for n, s, d in events):
        d = -d
        if name.startswith((SPAN_PREFIX, trace_reduce.HOST_PREFIX)):
            spans.append([name, s, d])
        elif (m := PJIT.match(name)) is not None:
            if s >= outer_end:
                outer_end, outer_fn = s + d, m.group(1)
        elif EXECUTE.search(name) and s >= exec_end:
            # one launch per call: an Execute nested in another (a backend
            # wrapping its own) is the same launch
            exec_end = s + d
            launches.append([outer_fn if s < outer_end else "", s])
    return spans, launches


def clock_lead(offsets: list) -> float:
    """How far the device's timestamps run ahead of the host's, in ns,
    from the named pairs' ``program start − launch``: minus their
    ``LEAD_QUANTILE`` quantile, or 0 where that is not negative. A program
    starts after its launch, soonest when the device waits for it, so the
    low end of the offsets is the launch latency less the clock's lead; a
    quantile, not the least offset, so that no single pair sets it. The
    lead is measured per trace: it differs from one process to the next."""
    if not offsets:
        return 0.0
    xs = sorted(offsets)
    return max(0.0, -xs[int(LEAD_QUANTILE * (len(xs) - 1))])


def pair(launches: list, modules: list) -> int:
    """The shift ``d`` that pairs module ``i`` (``(program, start_ns, …)``,
    in device order) with launch ``i + d`` (``(fn, start_ns)``, in host
    order). The device runs one program per launch, in launch order, so
    the two sequences differ only by programs launched before the trace
    began or run after it ended; ``d`` (``|d| ≤ MAX_SHIFT``) is the shift
    under which the most names agree."""
    want = [_name_key(m[0]) for m in modules]
    have = [_name_key("jit_" + fn) for fn, _ in launches]

    def agree(d):
        return sum(have[i + d] == w for i, w in enumerate(want)
                   if 0 <= i + d < len(have))
    return max(range(-MAX_SHIFT, MAX_SHIFT + 1), key=lambda d: (agree(d),
                                                                -abs(d)))


def _segments(spans: list) -> list:
    """``[(start, end, label)]``: where the innermost open span is the same,
    back to back over the spans' whole extent (``other`` between them)."""
    items = sorted(((s, s + d, n) for n, s, d in spans
                    if n != trace_reduce.WINDOW), key=lambda x: (x[0], -x[1]))
    segs, stack = [], []          # stack: (end, label, repro path)
    t = items[0][0] if items else 0.0

    def cut(upto):
        nonlocal t
        if upto > t:
            segs.append((t, upto, stack[-1][1] if stack else "other"))
            t = upto

    for s, e, name in items:
        while stack and stack[-1][0] <= s:
            cut(stack[-1][0])
            stack.pop()
        cut(s)
        if name.startswith(SPAN_PREFIX):
            parent = next((p for _, _, p in reversed(stack) if p), "")
            path = (parent + "/" if parent else "") + name[len(SPAN_PREFIX):]
            stack.append((e, path, path))
        else:
            stack.append((e, name, ""))
    while stack:
        cut(stack[-1][0])
        stack.pop()
    return segs


def _in_dispatch(label: str) -> bool:
    return any(part.startswith(DISPATCH) for part in label.split("/"))


def _dispatch_of(label: str) -> str:
    """``step/dispatch.hrot/modup/ntt`` → ``dispatch.hrot``; a label with no
    dispatch in its path stays as it is."""
    for part in label.split("/"):
        if part.startswith(DISPATCH):
            return part
    return label


@dataclasses.dataclass
class SpanTrace:
    device: trace_reduce.DeviceTrace
    spans: list                  # [(name, start_ns, dur_ns)], engine thread
    launches: list               # [(fn, start_ns)], in start order
    modules: list                # [(program, start_ns, dur_ns)], the whole
                                 # trace, in start order

    def __post_init__(self):
        self.segments = _segments(self.spans)
        self._starts = [s for s, _, _ in self.segments]

    def label_at(self, t_ns: float) -> str:
        i = bisect.bisect_right(self._starts, t_ns) - 1
        if i >= 0 and t_ns < self.segments[i][1]:
            return self.segments[i][2]
        return "other"

    def idle_by_span(self) -> dict:
        """Nanoseconds of device idle time by innermost label; the values
        sum to the window's idle time. Where the device's timestamps run
        ahead of the host's (``pairing["lead_ns"]``), a gap is labelled by
        the spans open that much later on the host."""
        lead_ns = self.pairing["lead_ns"]
        out = collections.Counter()
        segs, i = self.segments, 0
        for g0, d in self.device.gaps:
            t, g1 = g0 + lead_ns, g0 + lead_ns + d
            while i < len(segs) and segs[i][1] <= t:
                i += 1
            k = i
            while t < g1:
                if k < len(segs) and segs[k][0] <= t:
                    end = min(segs[k][1], g1)
                    out[segs[k][2]] += end - t
                    k += 1
                else:            # before the next segment, or after the last
                    end = min(segs[k][0], g1) if k < len(segs) else g1
                    out["other"] += end - t
                t = end
        return out

    def dispatch_host_ns(self) -> float:
        """Host nanoseconds inside ``repro.dispatch.*`` spans, clipped to
        the window."""
        w0, w1 = self.device.window
        return sum(max(0.0, min(s + d, w1) - max(s, w0))
                   for n, s, d in self.spans
                   if n.startswith(SPAN_PREFIX + DISPATCH))

    @functools.cached_property
    def pairing(self) -> dict:
        """The window's modules paired with the launches (:func:`pair`):
        counts, the pairs whose names disagree, how far the device clock
        leads the host's (:func:`clock_lead` of the named pairs), and over
        the named pairs the device nanoseconds in the window by dispatch
        label and by span path."""
        w0, w1 = self.device.window
        d = pair(self.launches, self.modules)
        by_op, by_path = collections.Counter(), collections.Counter()
        differ = collections.Counter()
        n = named = 0
        offsets = []
        for i, (prog, s, dur) in enumerate(self.modules):
            k = i + d
            launch = self.launches[k] if 0 <= k < len(self.launches) else None
            if launch is not None and \
                    _name_key("jit_" + launch[0]) == _name_key(prog):
                offsets.append(s - launch[1])
            else:
                launch = None
            inside = min(s + dur, w1) - max(s, w0)
            if inside <= 0:
                continue
            n += 1
            if launch is None:
                fn = self.launches[k][0] \
                    if 0 <= k < len(self.launches) else None
                differ[(fn, prog)] += 1
                continue
            named += 1
            label = self.label_at(launch[1])
            by_op[_dispatch_of(label)] += inside
            by_path[(label, prog)] += inside
        return {"modules": n, "named": named, "launches": len(self.launches),
                "shift": d, "lead_ns": clock_lead(offsets), "differ": differ,
                "by_op": by_op, "by_path": by_path}

    def breakdown(self, top: int = 25) -> dict:
        """The breakdown entries: ``idle_by_span`` (seconds, all of it,
        with the device clock's lead taken out), ``pairing``, and where at
        least ``MIN_PAIRED`` of the window's programs pair with a launch of
        their own name, ``device_by_op`` and the ``top`` largest
        ``device_by_span`` (``[span path, program, s]``)."""
        p = self.pairing
        share = p["named"] / p["modules"] if p["modules"] else 0.0
        idle = self.idle_by_span()
        out = {"idle_by_span": [[n, v * 1e-9] for n, v in idle.most_common()],
               "pairing": {
                   "modules": p["modules"], "named": p["named"],
                   "share": share, "launches": p["launches"],
                   "shift": p["shift"], "clock_lead_ms": p["lead_ns"] * 1e-6,
                   "names_differ": [[f"{fn} -> {prog}", c] for (fn, prog), c
                                    in p["differ"].most_common(8)]}}
        if p["modules"] and share >= MIN_PAIRED:
            out["device_by_op"] = [[n, v * 1e-9]
                                   for n, v in p["by_op"].most_common()]
            out["device_by_span"] = [[path, prog, v * 1e-9] for (path, prog), v
                                     in p["by_path"].most_common(top)]
        return out


def reduce(events: dict) -> SpanTrace | None:
    device = trace_reduce.reduce(events)
    if device is None:
        return None
    plane = next(p for p in sorted(events["devices"])
                 if trace_reduce.DEVICE_PLANE.match(p)
                 and events["devices"][p].get(trace_reduce.OPS_LINE))
    modules = sorted((trace_reduce.program_name(n), s, d) for n, s, d in
                     events["devices"][plane].get(trace_reduce.MODULES_LINE,
                                                  []))
    return SpanTrace(device=device,
                     spans=[tuple(s) for s in events.get("spans", [])],
                     launches=sorted((tuple(x) for x in
                                      events.get("launches", [])),
                                     key=lambda x: x[1]),
                     modules=sorted(modules, key=lambda m: m[1]))


# -- the per-layer metrics these spans give (see PERF.md §3) -----------------

def dispatch_host_ms_per_req(st: SpanTrace, completed: int):
    """Host milliseconds inside ``repro.dispatch.*`` spans in the window,
    per request completed in it."""
    ns = st.dispatch_host_ns()
    return 1e-6 * ns / completed if ns and completed else None


def idle_in_dispatch_share(st: SpanTrace):
    """% of the window in which the device is idle while the host is inside
    a ``repro.dispatch.*`` span (the dispatch subtrees of
    ``idle_by_span``)."""
    if not any(n.startswith(SPAN_PREFIX) for n, _, _ in st.spans):
        return None
    w0, w1 = st.device.window
    idle = sum(v for n, v in st.idle_by_span().items() if _in_dispatch(n))
    return 100.0 * idle / (w1 - w0)
