"""The trace reduction: on hand-made events, and on a trace recorded on a
TPU v5e (``testdata/``: the first 300 ms of a profiled window)."""
from pathlib import Path

import pytest

from fhebench import trace_reduce as tr

TESTDATA = Path(__file__).resolve().parent / "testdata"


def _events():
    ms = 1_000_000
    return {
        "devices": {
            "/device:TPU:0": {
                tr.OPS_LINE: [["fusion.1", 10 * ms, 10 * ms],
                              ["fusion.2", 15 * ms, 15 * ms],
                              ["custom-call.3", 50 * ms, 10 * ms],
                              ["fusion.9", 200 * ms, 5 * ms]],   # after
                tr.MODULES_LINE: [["jit_ntt(3)", 10 * ms, 20 * ms],
                                  ["jit_intt.1", 50 * ms, 10 * ms]]},
            "/device:TPU_SYSTEM:0": {tr.OPS_LINE: [["x", 0, 100 * ms]]},
        },
        "host": [["fhebench.window", 0, 100 * ms],
                 ["fhebench.step", 0, 40 * ms],
                 ["fhebench.block", 45 * ms, 55 * ms],
                 ["other.annotation", 0, 100 * ms]],
    }


def test_busy_idle_and_gaps_by_hand():
    t = tr.reduce(_events())
    assert t.window_s == pytest.approx(0.1)
    assert t.busy_s == pytest.approx(0.030)       # [10, 30] ∪ [50, 60]
    assert t.idle_share() == pytest.approx(0.7)
    ms = 1_000_000
    assert t.gaps == [(0, 10 * ms), (30 * ms, 20 * ms), (60 * ms, 40 * ms)]


def test_gaps_are_labelled_by_host_phase():
    bd = tr.reduce(_events()).breakdown()
    assert dict(bd["idle_gaps"]) == pytest.approx({"step": 0.030,
                                                   "block": 0.040})


def test_programs_by_name():
    t = tr.reduce(_events())
    assert t.program_count() == 2
    assert t.program_seconds(lambda n: n in ("jit_ntt", "jit_intt")) == \
        pytest.approx(0.030)
    assert tr.program_name("jit_auto_ks_pallas(17)") == "jit_auto_ks_pallas"


def test_no_window_or_no_device_reads_nothing():
    ev = _events()
    assert tr.reduce({"devices": ev["devices"], "host": []}) is None
    assert tr.reduce({"devices": {}, "host": ev["host"]}) is None


def test_recorded_chip_trace(tmp_path):
    ev = tr.load_events(TESTDATA / "mrs48_interactive_v5e.json.gz")
    t = tr.reduce(ev)
    assert t is not None
    assert t.window_s == pytest.approx(0.3)
    assert 0 < t.busy_s < t.window_s
    names = {n for n, _, _ in t.modules}
    assert {"jit_ntt", "jit_intt", "jit_auto_ks_pallas",
            "jit_bconv_matmul_pallas"} <= names
    bd = t.breakdown()
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) >= 1
    idle = sum(v for _, v in bd["idle_gaps"])
    assert idle == pytest.approx(t.window_s - t.busy_s, rel=1e-9)
    # the save/load round trip is exact
    tr.save_events(ev, tmp_path / "ev.json.gz")
    assert tr.reduce(tr.load_events(tmp_path / "ev.json.gz")) == t


def test_stalls_places_the_slowest_phases():
    from fhebench.cell import stalls
    phases = [("submit", 0.0, 0.001), ("step", 0.001, 0.2),
              ("block", 0.201, 0.05), ("step", 0.251, 2.5),
              ("step", 2.751, 0.3), ("harvest", 3.051, 0.01)]
    got = stalls(phases, n=2)
    assert got["step_s_median"] == pytest.approx(0.3)
    assert got["slowest"] == [["step", 0.251, 2.5], ["step", 2.751, 0.3]]
    assert stalls([]) == {"step_s_median": None, "slowest": []}
