"""The least HBM traffic of a kernel's job, from the HE ops dispatched.

An HE op is known by its plan key in ``PlanCache``: kind, basis (ℓ limbs),
batch B. Which kernel launches each kind makes today (``repro.core.ckks``):

* ``hmult`` / ``square``: one key switch of the batch — a ModUp of each of
  the J = ⌈ℓ/α⌉ digits (BConv: |D_j| limbs in, ℓ+K−|D_j| out) and one
  ModDown of both accumulators (BConv: 2·K limbs in, 2·ℓ out);
* ``hrot``: the same ModUp and ModDown, plus one ``auto_ks`` launch: the
  Galois permutation and evk MAC of B rotations over the extended basis
  Q_ℓ ∪ P (L = ℓ+K limbs);
* every other kind launches neither kernel.

The bytes are what the job must move at least, not what the code moves:
each input read once, each output written once; for ``auto_ks`` the digits
(J·B·L rows), one evk (both halves, J·L rows each) per distinct rotation —
a batch of one program rotates by one amount, so one evk — and the
(B, 2, L) output. Per-limb tables are left out. So a share of the roofline
computed from them cannot pass 100% unless the time leaves out work.
"""
from __future__ import annotations

WORD = 4                          # bytes per u32 residue
KEY_SWITCHING = ("hmult", "square", "hrot")


def digits(ell: int, alpha: int) -> list:
    """Limbs per key-switching digit at ℓ limbs."""
    return [min(alpha, ell - s) for s in range(0, ell, alpha)]


def bconv_bytes(kind: str, ell: int, batch: int, N: int, K: int,
                alpha: int) -> int:
    """Least bytes of the BConv launches of one dispatch of ``kind``."""
    if kind not in KEY_SWITCHING:
        return 0
    rows = sum(d + (ell + K - d) for d in digits(ell, alpha))   # ModUp
    rows += 2 * K + 2 * ell                                      # ModDown
    return WORD * N * batch * rows


def auto_ks_bytes(kind: str, ell: int, batch: int, N: int, K: int,
                  alpha: int, distinct_rotations: int = 1) -> int:
    """Least bytes of the ``auto_ks`` launch of one dispatch of ``kind``."""
    if kind != "hrot":
        return 0
    J, L = len(digits(ell, alpha)), ell + K
    rows = J * batch * L                        # hoisted digits, read once
    rows += 2 * J * L * distinct_rotations      # evk a- and b-halves
    rows += 2 * batch * L                       # (B, 2, L) output
    return WORD * N * rows


def window_bytes(plan_uses: dict, params, fn) -> int:
    """Σ over the window's dispatches of ``fn``'s bytes. ``plan_uses``
    maps a PlanCache key ``(kind, basis, batch, tenant, arg)`` to the
    number of dispatches through it."""
    total = 0
    for key, uses in plan_uses.items():
        kind, basis, batch = key[0], key[1], key[2]
        total += uses * fn(kind, len(basis), batch, params.N, params.K,
                           params.alpha)
    return total
