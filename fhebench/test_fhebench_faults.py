"""A whole run at a CPU size, past the look for a chip: the sound program is
correct; the float32 control and a timed path broken underneath are not.

The control is put in the program's place after the window and read by the
run's own check (``run_cell(control=True)``). The faults are planted in the
CKKS ops the batcher dispatches, so the run sees them exactly where the
chip's run would: a rotation that returns its input unchanged, half of a
key-switching batch left out (the other half's results reused for it), and
an answer altered where it is produced.
"""
import time

import pytest

from fhebench import cell
from fhebench.conftest import SMALL

SEED = 2 ** 31 + 12345            # more than 32 signed bits


def _run(root, workload="std.batch", control=False):
    return cell.run_cell(root, workload, SEED, 0.5, False,
                         time.perf_counter(), require_tpu=False,
                         control=control)


def test_sound_run_is_correct_and_control_is_not(small_root):
    result, _ = _run(small_root)
    checks = result["checks"]
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] > 0
    assert checks["answers_checked"]["value"] == \
        checks["answers_checked"]["limit"] > 0
    # the same run with the float32 control in the program's place
    ctrl, info = _run(small_root, control=True)
    assert info["program_correct"], info["program_checks"]
    assert not ctrl["correct"], ctrl["checks"]
    limit = SMALL["std-small"]["limit_widest_gap"]
    assert ctrl["checks"]["widest_gap"]["value"] > limit > \
        checks["widest_gap"]["value"]
    assert ctrl["checks"]["answers_checked"] == \
        info["program_checks"]["answers_checked"]


def test_control_fails_the_interactive_traffic(small_root):
    ctrl, info = _run(small_root, "std.interactive", control=True)
    assert info["program_correct"] and not ctrl["correct"], ctrl["checks"]


def _rotation_unchanged(ckks):
    return "hrot_many", lambda cts, rots, keys: list(cts)


def _half_batch_left_out(ckks):
    orig = ckks.hmult_many

    def half(c1s, c2s, keys):
        k = max(1, len(c1s) // 2)
        outs = orig(c1s[:k], c2s[:k], keys)
        return [outs[i % k] for i in range(len(c1s))]
    return "hmult_many", half


def _answer_altered(ckks):
    orig = ckks.hadd_many

    def altered(c1s, c2s, sub=False):
        outs = orig(c1s, c2s, sub=sub)
        c = outs[0]
        c.b.data = c.b.data.at[0, 0].add(1)
        return outs
    return "hadd_many", altered


@pytest.mark.parametrize("fault", [_rotation_unchanged, _half_batch_left_out,
                                   _answer_altered])
def test_a_broken_timed_path_is_not_correct(small_root, monkeypatch, fault):
    from repro.serve import batcher
    name, broken = fault(batcher.ckks)
    monkeypatch.setattr(batcher.ckks, name, broken)
    result, _ = _run(small_root)
    assert not result["correct"], result["checks"]
