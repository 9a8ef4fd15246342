"""The least-bytes functions against shapes worked out by hand."""
import pytest

from fhebench import work_bytes as wb

N, K, ALPHA = 1 << 16, 12, 12          # paper_full: dnum = 4 digits of 12
ROW = 4 * N                            # bytes of one limb of one poly


def test_digits():
    assert wb.digits(48, ALPHA) == [12, 12, 12, 12]
    assert wb.digits(46, ALPHA) == [12, 12, 12, 10]
    assert wb.digits(7, ALPHA) == [7]


def test_bconv_bytes_of_a_top_level_hmult_batch():
    # ModUp: 4 digits, each 12 limbs in and 48 + 12 − 12 = 48 out (240
    # rows); ModDown: 2·12 in, 2·48 out (120 rows); 8 ciphertexts
    assert wb.bconv_bytes("hmult", 48, 8, N, K, ALPHA) == 360 * 8 * ROW
    assert wb.bconv_bytes("square", 48, 8, N, K, ALPHA) == 360 * 8 * ROW


def test_bconv_bytes_of_a_low_level_rotation():
    # ℓ = 7: one digit of 7 limbs in, 7 + 12 − 7 = 12 out; ModDown 24 + 14
    assert wb.bconv_bytes("hrot", 7, 2, N, K, ALPHA) == (7 + 12 + 38) * 2 * ROW


def test_auto_ks_bytes_after_one_rescale():
    # ℓ = 46, L = 58, J = 4, B = 8: digits 4·8·58 rows, one evk with both
    # halves 2·4·58, output 2·8·58
    rows = 4 * 8 * 58 + 2 * 4 * 58 + 2 * 8 * 58
    assert rows == 3248
    assert wb.auto_ks_bytes("hrot", 46, 8, N, K, ALPHA) == rows * ROW


def test_auto_ks_bytes_counts_each_distinct_rotation_key():
    one = wb.auto_ks_bytes("hrot", 7, 8, N, K, ALPHA)
    two = wb.auto_ks_bytes("hrot", 7, 8, N, K, ALPHA, distinct_rotations=2)
    assert one == (8 * 19 + 2 * 19 + 2 * 8 * 19) * ROW
    assert two - one == 2 * 19 * ROW


@pytest.mark.parametrize("kind", ["hadd", "pmult", "rescale", "add_const"])
def test_kinds_without_the_kernel_move_nothing(kind):
    assert wb.bconv_bytes(kind, 48, 8, N, K, ALPHA) == 0
    assert wb.auto_ks_bytes(kind, 48, 8, N, K, ALPHA) == 0


def test_window_bytes_sums_plan_uses():
    class P:
        pass
    p = P()
    p.N, p.K, p.alpha = N, K, ALPHA
    basis46 = tuple(range(46))
    uses = {("hrot", basis46, 8, "tenant0", None): 3,
            ("hadd", basis46, 16, None, None): 5}
    want = 3 * wb.auto_ks_bytes("hrot", 46, 8, N, K, ALPHA)
    assert wb.window_bytes(uses, p, wb.auto_ks_bytes) == want
