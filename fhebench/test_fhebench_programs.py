"""The plain reference's own semantics, and its scale bookkeeping."""
import numpy as np
import pytest

from fhebench import reference


def test_reference_semantics():
    v = np.arange(8.0)
    inst = reference.Instance(
        ops=[("hrot", "r", ("x",), 3), ("pmult", "m", ("r",), "p"),
             ("rescale", "m", ("m",), None), ("add_const", "o", ("m",), 0.5)],
        inputs={"x": reference.Operand(v, 1.0, 3)},
        plaintexts={"p": reference.Operand(2 * np.ones(8), 1.0, 3)},
        outputs=("o",))
    out = reference.evaluate(inst)["o"]
    assert np.array_equal(out, 2 * np.roll(v, -3) + 0.5)
    assert reference.evaluate(inst, np.float32)["o"].dtype == np.float32


def test_scale_tracker_lands_a_pmult_on_its_target():
    q = (97, 101, 103, 107, 109)
    t = reference.ScaleTracker(q, 2)
    t.input("x", 5, 1000.0)
    t.pmult("y", "x", "w", np.ones(4), target_scale=250.0)
    assert t.scale("y") == pytest.approx(250.0)
    assert t.state["y"][0] == 3
    pt = t.plaintexts["w"]
    assert pt.limbs == 5 and pt.scale == pytest.approx(250.0 * 109 * 107
                                                       / 1000.0)
    assert [op[0] for op in t.ops] == ["pmult", "rescale"]
