"""The program's spans in a profiler trace (``fhebench.spans``): idle time
split by span, launches paired with device programs, the two span metrics,
and a CPU run of a cell under the profiler sink."""
import time

import pytest

from fhebench import spans
from fhebench import trace_reduce as tr

MS = 1_000_000


def _events(launches=(("ntt", 8.5), ("intt", 26), ("addmod", 99))):
    """Device busy [10, 30] ∪ [50, 60] ms of a 100 ms window; the engine in
    step → dispatch.hrot → modup → ntt, then moddown, then blocked."""
    return {
        "devices": {"/device:TPU:0": {
            tr.OPS_LINE: [["fusion.1", 10 * MS, 20 * MS],
                          ["custom-call.3", 50 * MS, 10 * MS]],
            tr.MODULES_LINE: [["jit_ntt(3)", 10 * MS, 20 * MS],
                              ["jit_intt.1", 50 * MS, 10 * MS]]}},
        "host": [["fhebench.window", 0, 100 * MS],
                 ["fhebench.step", 0, 40 * MS],
                 ["fhebench.block", 45 * MS, 55 * MS]],
        "spans": [["fhebench.window", 0, 100 * MS],
                  ["fhebench.step", 0, 40 * MS],
                  ["repro.step", 2 * MS, 36 * MS],
                  ["repro.dispatch.hrot", 5 * MS, 30 * MS],
                  ["repro.modup", 6 * MS, 14 * MS],
                  ["repro.ntt", 8 * MS, 4 * MS],
                  ["repro.moddown", 25 * MS, 8 * MS],
                  ["fhebench.block", 45 * MS, 55 * MS]],
        "launches": [[fn, t * MS] for fn, t in launches],
    }


def test_idle_is_split_exactly_by_the_innermost_span():
    st = spans.reduce(_events())
    idle = st.idle_by_span()
    assert {k: v / MS for k, v in idle.items()} == pytest.approx({
        "fhebench.step": 4, "step": 6, "step/dispatch.hrot": 3,
        "step/dispatch.hrot/modup": 2, "step/dispatch.hrot/modup/ntt": 2,
        # the gap [30, 50] crosses moddown's end, dispatch's, step's, ...
        "step/dispatch.hrot/moddown": 3, "other": 5, "fhebench.block": 45})
    assert sum(idle.values()) == pytest.approx(
        (st.device.window_s - st.device.busy_s) * 1e9, abs=1.0)
    bd = st.breakdown()
    assert sum(v for _, v in bd["idle_by_span"]) == pytest.approx(0.070)


def test_launches_pair_with_programs_by_name():
    st = spans.reduce(_events())
    bd = st.breakdown()
    # the last launch ran after the trace stopped: no program of its own
    assert bd["pairing"]["named"] == bd["pairing"]["modules"] == 2
    assert bd["pairing"]["shift"] == 0
    assert bd["pairing"]["clock_lead_ms"] == 0.0
    assert bd["device_by_op"] == [["dispatch.hrot", pytest.approx(0.030)]]
    assert bd["device_by_span"] == [
        ["step/dispatch.hrot/modup/ntt", "jit_ntt", pytest.approx(0.020)],
        ["step/dispatch.hrot/moddown", "jit_intt", pytest.approx(0.010)]]
    # programs launched before the trace began shift the pairing
    assert spans.pair([("ntt", 0), ("intt", 1)],
                      [("jit_intt", 5, 1), ("jit_addmod", 6, 1)]) == 1


def test_a_name_mismatch_leaves_device_by_op_out():
    st = spans.reduce(_events(launches=(("intt", 8.5), ("ntt", 26))))
    bd = st.breakdown()
    assert bd["pairing"]["named"] == 1 and bd["pairing"]["share"] == 0.5
    assert len(bd["pairing"]["names_differ"]) == 1
    assert "device_by_op" not in bd and "device_by_span" not in bd


def test_a_device_clock_ahead_of_the_host_is_taken_out():
    # each program's timestamp reads 1 ms before its own launch: the
    # device clock leads, and idle time is labelled 1 ms later on the host
    st = spans.reduce(_events(launches=(("ntt", 11), ("intt", 51))))
    p = st.breakdown()["pairing"]
    assert p["clock_lead_ms"] == pytest.approx(1.0)
    idle = st.idle_by_span()
    assert sum(idle.values()) == pytest.approx(70 * MS)
    assert idle["step/dispatch.hrot/modup/ntt"] == pytest.approx(3 * MS)
    assert idle["fhebench.step"] == pytest.approx(3 * MS)  # [1, 2), [38, 40)


def test_the_clock_lead_is_a_low_quantile_not_one_pair():
    # 300 pairs whose programs start 0.4 ms before their launch at the
    # least; one misread pair at -3 ms does not set the lead
    offsets = [-0.4 * MS + 1000 * i for i in range(299)] + [-3 * MS]
    assert spans.clock_lead(offsets) == pytest.approx(0.4 * MS - 1000)
    assert spans.clock_lead([]) == 0.0
    # programs that all start after their launch: no lead to take out
    assert spans.clock_lead([0.1 * MS, 0.2 * MS]) == 0.0

def test_span_metrics_by_hand():
    st = spans.reduce(_events())
    assert spans.dispatch_host_ms_per_req(st, 2) == pytest.approx(15.0)
    assert spans.dispatch_host_ms_per_req(st, 0) is None
    # idle while in dispatch: 3 + 2 + 2 + 3 ms of 100
    assert spans.idle_in_dispatch_share(st) == pytest.approx(10.0)
    # a trace without program spans (the parent's) reads nothing
    ev = _events()
    ev["spans"] = [s for s in ev["spans"] if s[0].startswith("fhebench.")]
    bare = spans.reduce(ev)
    assert spans.idle_in_dispatch_share(bare) is None
    assert spans.dispatch_host_ms_per_req(bare, 2) is None
    assert dict(bare.idle_by_span()) == pytest.approx(
        {"fhebench.step": 20 * MS, "other": 5 * MS,
         "fhebench.block": 45 * MS})


def _inside(inner, outers):
    s, d = inner[1], inner[2]
    return any(o[1] <= s and s + d <= o[1] + o[2] for o in outers)


def test_cpu_run_under_the_sink_nests_the_engine_spans(small_root):
    from fhebench import span_run
    from repro.runtime import tracing
    import jax
    start_trace = jax.profiler.start_trace
    result, info, events = span_run.run(
        small_root, "std.interactive", 2 ** 31 + 7, 0.5,
        time.perf_counter(), require_tpu=False)
    assert result["correct"], result["checks"]
    # the sink and the profiler wrapper are gone again
    assert tracing.span("x") is tracing.span("y")
    assert jax.profiler.start_trace is start_trace
    by = {}
    for s in events["spans"]:
        by.setdefault(s[0], []).append(s)
    steps = by["repro.step"]
    dispatches = [s for n, v in by.items() if n.startswith("repro.dispatch.")
                  for s in v]
    assert {n for n in by if n.startswith("repro.dispatch.")} >= {
        "repro.dispatch.hmult", "repro.dispatch.hrot",
        "repro.dispatch.rescale", "repro.dispatch.hadd"}
    assert all(_inside(s, steps) for s in dispatches)
    for child in ("repro.plan", "repro.scatter", "repro.modup",
                  "repro.moddown", "repro.rescale"):
        assert by[child] and all(_inside(s, dispatches) for s in by[child])
    for child in ("repro.fill", "repro.retire"):
        assert all(_inside(s, steps) for s in by[child])
    assert all(_inside(s, by["fhebench.step"]) for s in steps)
    # every span lies in the trace, each jitted launch is named
    launched = {fn for fn, _ in events["launches"]}
    assert {"ntt", "intt"} <= launched


def test_recorded_chip_trace_with_spans():
    """The first 300 ms of a traced ``mrs48.interactive`` window on a TPU
    v5e, with the program's spans and launch events."""
    from pathlib import Path
    ev = tr.load_events(Path(__file__).resolve().parent / "testdata"
                        / "mrs48_interactive_spans_v5e.json.gz")
    st = spans.reduce(ev)
    assert st.device.window_s == pytest.approx(0.3)
    bd = st.breakdown()
    idle = sum(v for _, v in bd["idle_by_span"])
    assert idle == pytest.approx(st.device.window_s - st.device.busy_s,
                                 abs=1e-6)
    # nearly all idle time inside the engine's step lies in a named child
    labels = dict(bd["idle_by_span"])
    in_step = sum(v for n, v in labels.items()
                  if n == "fhebench.step" or n.split("/")[0] == "step")
    bare = labels.get("step", 0.0) + labels.get("fhebench.step", 0.0)
    assert in_step > 0 and bare < 0.1 * in_step
    # launches pair with programs in order; where names differ the
    # programs are layout-only ones that one cached executable serves
    p = bd["pairing"]
    assert p["named"] >= 0.9 * p["modules"] > 0
    layout = {"squeeze", "broadcast_in_dim", "dynamic_slice", "reshape"}
    for pair_names, _ in p["names_differ"]:
        fn, prog = pair_names.split(" -> ")
        assert fn in layout and prog[len("jit_"):] in layout
    assert 0.5 < p["clock_lead_ms"] < 3.0
    assert ("device_by_op" in bd) == (p["share"] >= spans.MIN_PAIRED)
    named_s = sum(st.pairing["by_op"].values()) * 1e-9
    module_s = sum(d for _, _, d in st.device.modules) * 1e-9
    assert named_s == pytest.approx(module_s, rel=0.05)
    assert spans.idle_in_dispatch_share(st) > 10.0
