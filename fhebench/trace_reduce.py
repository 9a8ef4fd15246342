"""Reduces a ``jax.profiler`` trace of the window to device metrics.

Device planes (``/device:TPU:<n>``) carry two lines that matter here:
``XLA Modules`` — one event per execution of a compiled program, named
after it (``jit_ntt(…)``) — and ``XLA Ops`` — one event per HLO op or
kernel inside it. The host plane carries the benchmark's own
``fhebench.<phase>`` annotations on the same clock. From these:

* busy time: the union of the op intervals inside the ``fhebench.window``
  annotation, averaged over the devices that ran anything;
* idle gaps: the rest of the window, each labelled with the innermost
  benchmark phase the host was in at the gap's midpoint;
* program time and count by program name (numeric suffixes dropped).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import gzip
import json
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PREFIX = "fhebench."
WINDOW = HOST_PREFIX + "window"


def program_name(event_name: str) -> str:
    """``jit_ntt(123)`` / ``jit_ntt.4`` → ``jit_ntt``."""
    return re.sub(r"(\(\d+\)|\.\d+)+$", "", event_name.strip())


def extract(trace_dir) -> dict:
    """The events this module reads, as plain data:
    ``{"devices": {plane: {line: [[name, start_ns, dur_ns], …]}},
       "host": [[name, start_ns, dur_ns], …]}``."""
    import jax
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        return {"devices": {}, "host": []}
    pd = jax.profiler.ProfileData.from_file(str(files[-1]))
    devices, host = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [[e.name, e.start_ns, e.duration_ns]
                                        for e in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host}


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class DeviceTrace:
    window: tuple                # (start_ns, end_ns) of fhebench.window
    busy_ns: float               # union of op time in the window, per device
    gaps: list                   # [(start_ns, dur_ns)] on the first device
    modules: list                # [(name, start_ns, dur_ns)] in the window
    host: list                   # [(name, start_ns, dur_ns)] phases

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / (self.window[1] - self.window[0])

    def program_seconds(self, match) -> float:
        """Device seconds of the programs whose name satisfies ``match``."""
        return sum(d for n, _, d in self.modules if match(n)) * 1e-9

    def program_count(self) -> int:
        return len(self.modules)

    def phase_at(self, t_ns: float) -> str:
        """The innermost benchmark phase the host was in at ``t_ns`` (the
        phases inside the window do not overlap)."""
        if not hasattr(self, "_phases"):
            # at equal starts the inner (shorter) phase sorts last
            self._phases = sorted(((s, s + d, n) for n, s, d in self.host
                                   if n != WINDOW),
                                  key=lambda p: (p[0], -p[1]))
            self._starts = [p[0] for p in self._phases]
        i = bisect.bisect_right(self._starts, t_ns) - 1
        while i >= 0:
            s, e, n = self._phases[i]
            if e >= t_ns:
                return n[len(HOST_PREFIX):]
            if t_ns - s > 60e9:
                break
            i -= 1
        return "other"

    def breakdown(self, top: int = 10) -> dict:
        by_prog = collections.Counter()
        for n, _, d in self.modules:
            by_prog[n] += d * 1e-9
        idle = collections.Counter()
        for s, d in self.gaps:
            idle[self.phase_at(s + d / 2)] += d * 1e-9
        return {"device_ops": [[n, v] for n, v in by_prog.most_common(top)],
                "idle_gaps": [[n, v] for n, v in idle.most_common(top)]}


def reduce(events: dict) -> DeviceTrace | None:
    """``extract``'s data → :class:`DeviceTrace`; None when the trace has
    no window annotation or no device ops in it."""
    wins = [(s, s + d) for n, s, d in events["host"] if n == WINDOW]
    if not wins:
        return None
    w0, w1 = wins[-1]
    busy, first_gaps, modules = [], None, []

    def clip(evs):
        return [(program_name(n), max(s, w0), min(s + d, w1) - max(s, w0))
                for n, s, d in evs if s < w1 and s + d > w0]

    for plane in sorted(events["devices"]):
        if not DEVICE_PLANE.match(plane):
            continue
        lines = events["devices"][plane]
        plane_ops = clip(lines.get(OPS_LINE, []))
        if not plane_ops:
            continue
        u = _union((s, s + d) for _, s, d in plane_ops)
        busy.append(sum(e - s for s, e in u))
        if first_gaps is None:
            first_gaps, t = [], w0
            for s, e in u:
                if s > t:
                    first_gaps.append((t, s - t))
                t = max(t, e)
            if w1 > t:
                first_gaps.append((t, w1 - t))
        modules.extend(clip(lines.get(MODULES_LINE, [])))
    if not busy:
        return None
    host = [(n, s, d) for n, s, d in events["host"]
            if n.startswith(HOST_PREFIX)]
    return DeviceTrace(window=(w0, w1), busy_ns=sum(busy) / len(busy),
                       gaps=first_gaps, modules=modules, host=host)


def load(trace_dir) -> DeviceTrace | None:
    return reduce(extract(trace_dir))


def save_events(events: dict, path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load_events(path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)
