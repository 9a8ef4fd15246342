"""Negacyclic NTT — iterative reference + the recomposable four-step NTT (paper §III-B).

Three implementations of the same transform:

* :func:`ntt_lanes` / :func:`intt_lanes` — the production transform of every
  single-device ``RnsPoly.to_ntt``/``to_coeff`` (programs ``jit_ntt`` and
  ``jit_intt``), at every N ≥ 4, on :func:`lane_split`'s R×C split
  (256×256 at N = 2¹⁶): the four-step dataflow laid out for a TPU, every
  butterfly along the rows, the 128-wide lane axis never split.

* :func:`ntt` / :func:`intt` — fused iterative Cooley-Tukey / Gentleman-Sande
  (Longa-Naehrig) with Shoup multipliers.  This is the *oracle*.

* :func:`four_step_ntt` / :func:`four_step_intt` — the paper's recomposable
  dataflow: a length-N polynomial viewed as an R×C matrix; an R-point
  *negacyclic* column NTT (root ψ^C), the inter-step twiddle ψ^{(2k₁+1)n₂},
  and a C-point *cyclic* row DFT (root ω=ψ^{2R}).  ``R`` is the recomposition
  parameter — CiFHER's "number of submodules" knob.  Every power-of-two split
  must produce identical results (validated in tests); the Pallas kernel in
  ``repro.kernels.ntt`` executes this dataflow tile-by-tile in VMEM.

Hot-path design (EXPERIMENTS.md §Perf):

* **Gather-free**: the only data permutation an iterative radix-2 NTT needs is
  bit reversal, and bit reversal of 2^k indices is exactly "reshape to [2]*k,
  reverse the axes" — :func:`bitrev_permute` expresses it as a transpose that
  XLA fuses, instead of a ``jnp.take`` gather.  All twiddle tables are
  pre-permuted host-side (``psi_rev`` fused-CT order; ``row_stage`` stage-major
  DIT order) so every stage reads a contiguous slice.
* **Lazy reduction**: butterflies keep values in [0, 2q)
  (:func:`repro.core.modmath.addmod_lazy` et al.) — two selects per butterfly
  instead of three, no select in the Shoup product — with a single
  :func:`~repro.core.modmath.reduce_once` pass (forward) or the final
  n⁻¹/Shoup multiply (inverse) restoring [0, q) at the boundary.
* **Device-resident constants**: callers go through
  :mod:`repro.core.const_cache` so tables are staged to the device once per
  (basis, N[, R]) instead of ``jnp.asarray`` per call.

The previous eager implementations are kept as ``*_eager`` — they are the
before-side of the perf comparison in ``benchmarks/bench_ntt.py`` and an extra
parity oracle in tests.

All transforms use NATURAL-order inputs and outputs:
    ntt(a)[k] = Σₙ a[n]·ψ^{(2k+1)n} mod q  —  evaluation at the odd root ψ^{2k+1}.
Natural ordering keeps automorphism a clean index permutation (§II-C).

Shapes: ``x`` is ``(..., ℓ, N)`` u32 with one modulus per limb row; the limb
tables are stacked ``(ℓ, N)`` arrays built by :func:`stacked_ntt_consts`.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import modmath as mm
from . import rns


class NttConsts(NamedTuple):
    """Stacked per-limb NTT constants for a prime basis (pytree of arrays).

    NOTE: exactly 12 fields, in this order — ``repro.core.distributed``
    re-assembles instances positionally from flat shard_map operands.
    """
    q: np.ndarray                  # (ℓ, 1) u32
    psi_rev: np.ndarray            # (ℓ, N) u32 — fused CT forward table
    psi_rev_shoup: np.ndarray      # (ℓ, N)
    psi_inv_rev: np.ndarray        # (ℓ, N) — fused GS inverse table
    psi_inv_rev_shoup: np.ndarray  # (ℓ, N)
    n_inv: np.ndarray              # (ℓ, 1)
    n_inv_shoup: np.ndarray        # (ℓ, 1)
    qinv_neg: np.ndarray           # (ℓ, 1) — Montgomery -q⁻¹ mod 2³²
    r2: np.ndarray                 # (ℓ, 1) — 2⁶⁴ mod q
    mu_hi: np.ndarray              # (ℓ, 1) — Barrett floor(2⁶²/q) split
    mu_lo: np.ndarray              # (ℓ, 1)
    brev: np.ndarray               # (N,) i32 — bit-reversal permutation


@functools.lru_cache(maxsize=None)
def stacked_ntt_consts(basis: tuple[int, ...], N: int) -> NttConsts:
    tabs = [rns.prime_tables(q, N) for q in basis]
    stack = lambda f: np.stack([f(t) for t in tabs])
    col = lambda f: np.array([[f(t)] for t in tabs], dtype=np.uint32)
    return NttConsts(
        q=col(lambda t: t.q),
        psi_rev=stack(lambda t: t.psi_rev),
        psi_rev_shoup=stack(lambda t: t.psi_rev_shoup),
        psi_inv_rev=stack(lambda t: t.psi_inv_rev),
        psi_inv_rev_shoup=stack(lambda t: t.psi_inv_rev_shoup),
        n_inv=col(lambda t: t.n_inv),
        n_inv_shoup=col(lambda t: t.n_inv_shoup),
        qinv_neg=col(lambda t: t.qinv_neg),
        r2=col(lambda t: t.r2),
        mu_hi=col(lambda t: t.mu_hi),
        mu_lo=col(lambda t: t.mu_lo),
        brev=rns.bitrev_indices(N).astype(np.int32),
    )


def balanced_submodules(N: int) -> int:
    """CiFHER's balanced default submodule count: R = √N (power of two).

    The untuned fallback for the four-step R×C split — the kernel wrapper
    (``repro.kernels.ntt.ops``) and the autotuner
    (``repro.kernels.autotune``) both resolve R through here when no tuned
    entry exists for the shape, so the recomposition policy has ONE home.
    """
    R = 1
    while R * R < N:
        R *= 2
    return R


def valid_submodules(N: int, R) -> bool:
    """True when R is a usable four-step split: power of two with C = N/R ≥ 2."""
    return (isinstance(R, int) and R >= 2 and (R & (R - 1)) == 0
            and N % R == 0 and N // R >= 2)


# ----------------------------------------------------------------------------
# Gather-free bit reversal
# ----------------------------------------------------------------------------

def bitrev_permute(x):
    """Bit-reversal permutation of the last axis (length 2^k) without a gather.

    Reversing the k bits of an index is reshaping to k axes of extent 2 and
    reversing the axis order — a pure transpose, which compiles to data
    movement XLA can fuse (and that a VMEM-resident Pallas tile performs as
    register shuffles) instead of the indexed gather ``jnp.take(x, brev)``.
    Works on numpy and jax arrays alike; self-inverse.
    """
    N = x.shape[-1]
    k = N.bit_length() - 1
    if k <= 1:
        return x
    lead = x.shape[:-1]
    nl = len(lead)
    y = x.reshape(*lead, *([2] * k))
    perm = tuple(range(nl)) + tuple(nl + k - 1 - i for i in range(k))
    return y.transpose(perm).reshape(*lead, N)


# ----------------------------------------------------------------------------
# Iterative fused CT / GS (the oracle)
# ----------------------------------------------------------------------------

def _ntt_lazy(x, c: NttConsts):
    """Fused-CT forward stages in the lazy range, natural-order output.

    Input any values < 2q; output in [0, 2q) — callers either chain more lazy
    stages (four-step) or finish with :func:`~repro.core.modmath.reduce_once`.
    """
    N = x.shape[-1]
    q = c.q[..., None]  # (ℓ, 1, 1) broadcasting against (..., ℓ, m, t)
    two_q = q + q
    lead = x.shape[:-1]
    m, t = 1, N
    while m < N:
        t //= 2
        y = x.reshape(*lead, m, 2, t)
        a, b = y[..., 0, :], y[..., 1, :]
        w = c.psi_rev[:, m:2 * m][:, :, None]
        ws = c.psi_rev_shoup[:, m:2 * m][:, :, None]
        bw = mm.mulmod_shoup_lazy(b, w, ws, q)
        x = jnp.stack([mm.addmod_lazy(a, bw, two_q),
                       mm.submod_lazy(a, bw, two_q)], axis=-2)
        x = x.reshape(*lead, N)
        m *= 2
    return bitrev_permute(x)  # bit-reversed → natural, gather-free


def ntt(x, c: NttConsts):
    """Forward negacyclic NTT over the last axis; natural-order in/out."""
    return mm.reduce_once(_ntt_lazy(x, c), c.q)


def intt(x, c: NttConsts):
    """Inverse negacyclic NTT over the last axis; natural-order in/out.

    Accepts lazy inputs (any values < 2q); output fully reduced in [0, q)
    by the final n⁻¹ Shoup multiply.
    """
    N = x.shape[-1]
    q = c.q[..., None]
    two_q = q + q
    lead = x.shape[:-1]
    x = bitrev_permute(x)  # natural → bit-reversed, gather-free
    t, m = 1, N
    while m > 1:
        h = m // 2
        y = x.reshape(*lead, h, 2, t)
        a, b = y[..., 0, :], y[..., 1, :]
        w = c.psi_inv_rev[:, h:2 * h][:, :, None]
        ws = c.psi_inv_rev_shoup[:, h:2 * h][:, :, None]
        u = mm.addmod_lazy(a, b, two_q)
        v = mm.mulmod_shoup_lazy(mm.submod_lazy(a, b, two_q), w, ws, q)
        x = jnp.stack([u, v], axis=-2).reshape(*lead, N)
        t *= 2
        m = h
    return mm.mulmod_shoup(x, c.n_inv, c.n_inv_shoup, c.q)


# -- previous eager path (before-side of the perf comparison; extra oracle) ---

def ntt_eager(x, c: NttConsts):
    """Pre-overhaul forward NTT: eager [0, q) reduction + ``jnp.take`` gather."""
    N = x.shape[-1]
    q = c.q[..., None]
    lead = x.shape[:-1]
    m, t = 1, N
    while m < N:
        t //= 2
        y = x.reshape(*lead, m, 2, t)
        a, b = y[..., 0, :], y[..., 1, :]
        w = jnp.asarray(c.psi_rev[:, m:2 * m])[:, :, None]
        ws = jnp.asarray(c.psi_rev_shoup[:, m:2 * m])[:, :, None]
        bw = mm.mulmod_shoup(b, w, ws, q)
        x = jnp.stack([mm.addmod(a, bw, q), mm.submod(a, bw, q)], axis=-2)
        x = x.reshape(*lead, N)
        m *= 2
    return jnp.take(x, jnp.asarray(c.brev), axis=-1)


def intt_eager(x, c: NttConsts):
    """Pre-overhaul inverse NTT: eager reduction + ``jnp.take`` gather."""
    N = x.shape[-1]
    q = c.q[..., None]
    lead = x.shape[:-1]
    x = jnp.take(x, jnp.asarray(c.brev), axis=-1)
    t, m = 1, N
    while m > 1:
        h = m // 2
        y = x.reshape(*lead, h, 2, t)
        a, b = y[..., 0, :], y[..., 1, :]
        w = jnp.asarray(c.psi_inv_rev[:, h:2 * h])[:, :, None]
        ws = jnp.asarray(c.psi_inv_rev_shoup[:, h:2 * h])[:, :, None]
        u = mm.addmod(a, b, q)
        v = mm.mulmod_shoup(mm.submod(a, b, q), w, ws, q)
        x = jnp.stack([u, v], axis=-2).reshape(*lead, N)
        t *= 2
        m = h
    return mm.mulmod_shoup(x, c.n_inv, c.n_inv_shoup, c.q)


# ----------------------------------------------------------------------------
# Four-step recomposable NTT (paper §III-B dataflow)
# ----------------------------------------------------------------------------

class FourStepConsts(NamedTuple):
    """Stacked per-limb constants for the R×C four-step decomposition."""
    R: int
    C: int
    q: np.ndarray                # (ℓ, 1) u32
    col: NttConsts               # stacked negacyclic tables, length R, root ψ^C
    twiddle: np.ndarray          # (ℓ, R, C) — ψ^{(2k₁+1)n₂}, k₁ natural
    twiddle_shoup: np.ndarray
    twiddle_inv: np.ndarray
    twiddle_inv_shoup: np.ndarray
    row_pow: np.ndarray          # (ℓ, C/2) — ω^i, ω = ψ^{2R}
    row_pow_shoup: np.ndarray
    row_pow_inv: np.ndarray
    row_pow_inv_shoup: np.ndarray
    c_inv: np.ndarray            # (ℓ, 1)
    c_inv_shoup: np.ndarray
    brev_c: np.ndarray           # (C,) i32
    # pre-permuted stage-major DIT twiddles (stage m = slice [m-1, 2m-1))
    row_stage: np.ndarray        # (ℓ, C-1)
    row_stage_shoup: np.ndarray
    row_stage_inv: np.ndarray
    row_stage_inv_shoup: np.ndarray


@functools.lru_cache(maxsize=None)
def stacked_four_step_consts(basis: tuple[int, ...], N: int, R: int) -> FourStepConsts:
    tabs = [rns.four_step_tables(q, N, R) for q in basis]
    C = N // R
    stack = lambda f: np.stack([f(t) for t in tabs])
    colv = lambda f: np.array([[f(t)] for t in tabs], dtype=np.uint32)
    col_consts = NttConsts(
        q=colv(lambda t: t.col.q),
        psi_rev=stack(lambda t: t.col.psi_rev),
        psi_rev_shoup=stack(lambda t: t.col.psi_rev_shoup),
        psi_inv_rev=stack(lambda t: t.col.psi_inv_rev),
        psi_inv_rev_shoup=stack(lambda t: t.col.psi_inv_rev_shoup),
        n_inv=colv(lambda t: t.col.n_inv),
        n_inv_shoup=colv(lambda t: t.col.n_inv_shoup),
        qinv_neg=colv(lambda t: t.col.qinv_neg),
        r2=colv(lambda t: t.col.r2),
        mu_hi=colv(lambda t: t.col.mu_hi),
        mu_lo=colv(lambda t: t.col.mu_lo),
        brev=rns.bitrev_indices(R).astype(np.int32),
    )
    return FourStepConsts(
        R=R, C=C,
        q=colv(lambda t: t.col.q),
        col=col_consts,
        twiddle=stack(lambda t: t.twiddle),
        twiddle_shoup=stack(lambda t: t.twiddle_shoup),
        twiddle_inv=stack(lambda t: t.twiddle_inv),
        twiddle_inv_shoup=stack(lambda t: t.twiddle_inv_shoup),
        row_pow=stack(lambda t: t.row_pow),
        row_pow_shoup=stack(lambda t: t.row_pow_shoup),
        row_pow_inv=stack(lambda t: t.row_pow_inv),
        row_pow_inv_shoup=stack(lambda t: t.row_pow_inv_shoup),
        c_inv=colv(lambda t: t.c_inv),
        c_inv_shoup=colv(lambda t: t.c_inv_shoup),
        brev_c=rns.bitrev_indices(C).astype(np.int32),
        row_stage=stack(lambda t: t.row_stage),
        row_stage_shoup=stack(lambda t: t.row_stage_shoup),
        row_stage_inv=stack(lambda t: t.row_stage_inv),
        row_stage_inv_shoup=stack(lambda t: t.row_stage_inv_shoup),
    )


def _cyclic_dft_lazy(x, stage_tab, stage_tab_shoup, q):
    """Length-C cyclic DIT NTT over the last axis, natural-order in/out.

    Lazy-range butterflies: inputs < 2q → outputs in [0, 2q).  ``stage_tab``
    is the (ℓ, C-1) stage-major table — stage m reads the contiguous slice
    [m-1, 2m-1) (no strided subsampling, no gather).  q: (ℓ, 1).
    """
    C = x.shape[-1]
    lead = x.shape[:-1]
    qb = q[..., None]
    two_q = qb + qb
    x = bitrev_permute(x)
    m = 1
    while m < C:
        y = x.reshape(*lead[:-1], lead[-1] * (C // (2 * m)), 2, m)
        a, b = y[..., 0, :], y[..., 1, :]
        w = stage_tab[:, m - 1:2 * m - 1][:, None, :]        # (ℓ, 1, m)
        ws = stage_tab_shoup[:, m - 1:2 * m - 1][:, None, :]
        bw = mm.mulmod_shoup_lazy(b, w, ws, qb)
        x = jnp.stack([mm.addmod_lazy(a, bw, two_q),
                       mm.submod_lazy(a, bw, two_q)], axis=-2)
        x = x.reshape(*lead, C)
        m *= 2
    return x


def _cyclic_dft(x, pow_tab, pow_tab_shoup, brev_c, q):
    """Length-C cyclic DIT NTT, fully-reduced in/out (shard_map-compat API).

    ``pow_tab``: (ℓ, C/2) powers ω^i; stage-m twiddles are the stride-C/(2m)
    subsampling.  ``brev_c`` is accepted for operand-signature compatibility
    with ``repro.core.distributed`` but the data permutation itself is the
    gather-free :func:`bitrev_permute`.
    """
    del brev_c
    C = x.shape[-1]
    lead = x.shape[:-1]
    qb = q[..., None]
    two_q = qb + qb
    x = bitrev_permute(x)
    m = 1
    while m < C:
        y = x.reshape(*lead[:-1], lead[-1] * (C // (2 * m)), 2, m)
        a, b = y[..., 0, :], y[..., 1, :]
        stride = C // (2 * m)
        w = pow_tab[:, ::stride][:, :m][:, None, :]          # (ℓ, 1, m)
        ws = pow_tab_shoup[:, ::stride][:, :m][:, None, :]
        bw = mm.mulmod_shoup_lazy(b, w, ws, qb)
        x = jnp.stack([mm.addmod_lazy(a, bw, two_q),
                       mm.submod_lazy(a, bw, two_q)], axis=-2)
        x = x.reshape(*lead, C)
        m *= 2
    return mm.reduce_once(x, qb)


def four_step_ntt(x, fc: FourStepConsts):
    """Forward negacyclic NTT via the paper's R×C four-step dataflow.

    Input/output natural order, identical to :func:`ntt` for every valid R.
    Data is viewed as A[n₁, n₂] = a[C·n₁ + n₂]; the output is re-flattened so
    that â[k₁ + R·k₂] = B[k₁, k₂].  All three phases run in the lazy range
    with a single correction pass at the end.
    """
    R, C = fc.R, fc.C
    lead = x.shape[:-1]
    A = x.reshape(*lead, R, C)
    # 1) R-point negacyclic NTT along columns (axis -2), root ψ^C.
    #    Move n₂ before the limb axis so the (ℓ, R) tables broadcast.
    A = jnp.moveaxis(A, -1, -3)                  # (..., C, ℓ, R)
    A = _ntt_lazy(A, fc.col)
    A = jnp.moveaxis(A, -3, -1)                  # (..., ℓ, R, C), k₁ natural
    # 2) inter-step twiddle ψ^{(2k₁+1)·n₂} — selectless lazy Shoup product
    A = mm.mulmod_shoup_lazy(A, fc.twiddle, fc.twiddle_shoup, fc.q[..., None])
    # 3) C-point cyclic DFT along rows (axis -1), root ω = ψ^{2R}.
    A = _cyclic_dft_lazy(A, fc.row_stage, fc.row_stage_shoup, fc.q)
    A = mm.reduce_once(A, fc.q[..., None])
    # 4) transpose so that flattening yields â[k₁ + R·k₂].
    return jnp.swapaxes(A, -1, -2).reshape(*lead, R * C)


def four_step_intt(x, fc: FourStepConsts):
    """Inverse of :func:`four_step_ntt`; natural order in/out."""
    R, C = fc.R, fc.C
    lead = x.shape[:-1]
    B = x.reshape(*lead, C, R)
    B = jnp.swapaxes(B, -1, -2)                  # (..., ℓ, R, C), [k₁, k₂]
    # inverse row DFT (ω^{-1}), then scale by C⁻¹ — all lazy
    B = _cyclic_dft_lazy(B, fc.row_stage_inv, fc.row_stage_inv_shoup, fc.q)
    B = mm.mulmod_shoup_lazy(B, fc.c_inv[..., None], fc.c_inv_shoup[..., None],
                             fc.q[..., None])
    # inverse twiddle
    B = mm.mulmod_shoup_lazy(B, fc.twiddle_inv, fc.twiddle_inv_shoup,
                             fc.q[..., None])
    # inverse column negacyclic NTT (accepts lazy inputs; includes R⁻¹ scaling
    # whose full Shoup reduction restores [0, q))
    B = jnp.moveaxis(B, -1, -3)                  # (..., C, ℓ, R)
    B = intt(B, fc.col)
    B = jnp.moveaxis(B, -3, -1)                  # (..., ℓ, R, C) = A[n₁, n₂]
    return B.reshape(*lead, R * C)


def four_step_ntt_eager(x, fc: FourStepConsts):
    """Pre-overhaul four-step forward (eager reduction, gathers, asarray)."""
    R, C = fc.R, fc.C
    lead = x.shape[:-1]
    A = x.reshape(*lead, R, C)
    A = jnp.moveaxis(A, -1, -3)
    A = ntt_eager(A, fc.col)
    A = jnp.moveaxis(A, -3, -1)
    A = mm.mulmod_shoup(A, jnp.asarray(fc.twiddle), jnp.asarray(fc.twiddle_shoup),
                        fc.q[..., None])
    A = _cyclic_dft_eager(A, fc.row_pow, fc.row_pow_shoup, fc.brev_c, fc.q)
    return jnp.swapaxes(A, -1, -2).reshape(*lead, R * C)


def four_step_intt_eager(x, fc: FourStepConsts):
    """Pre-overhaul four-step inverse (eager reduction, gathers, asarray)."""
    R, C = fc.R, fc.C
    lead = x.shape[:-1]
    B = x.reshape(*lead, C, R)
    B = jnp.swapaxes(B, -1, -2)
    B = _cyclic_dft_eager(B, fc.row_pow_inv, fc.row_pow_inv_shoup, fc.brev_c, fc.q)
    B = mm.mulmod_shoup(B, fc.c_inv[..., None], fc.c_inv_shoup[..., None],
                        fc.q[..., None])
    B = mm.mulmod_shoup(B, jnp.asarray(fc.twiddle_inv),
                        jnp.asarray(fc.twiddle_inv_shoup), fc.q[..., None])
    B = jnp.moveaxis(B, -1, -3)
    B = intt_eager(B, fc.col)
    B = jnp.moveaxis(B, -3, -1)
    return B.reshape(*lead, R * C)


def _cyclic_dft_eager(x, pow_tab, pow_tab_shoup, brev_c, q):
    """Pre-overhaul cyclic DIT NTT: gather bit-reversal + eager reduction."""
    C = x.shape[-1]
    lead = x.shape[:-1]
    qb = q[..., None]
    x = jnp.take(x, jnp.asarray(brev_c), axis=-1)
    m = 1
    while m < C:
        y = x.reshape(*lead[:-1], lead[-1] * (C // (2 * m)), 2, m)
        a, b = y[..., 0, :], y[..., 1, :]
        stride = C // (2 * m)
        w = jnp.asarray(pow_tab[:, ::stride][:, :m])[:, None, :]
        ws = jnp.asarray(pow_tab_shoup[:, ::stride][:, :m])[:, None, :]
        bw = mm.mulmod_shoup(b, w, ws, qb)
        x = jnp.stack([mm.addmod(a, bw, qb), mm.submod(a, bw, qb)], axis=-2)
        x = x.reshape(*lead, C)
        m *= 2
    return x


# ----------------------------------------------------------------------------
# Lane-dense four-step NTT (the production single-device transform)
# ----------------------------------------------------------------------------

LANES = 128        # width of the TPU's lane axis (the last axis of an array)
STAGE_GROUP = 4    # radix-2 butterfly stages computed per pass over the data


def lane_split(N: int) -> tuple[int, int]:
    """The R×C split of the lane-dense transform, for any N ≥ 4.

    C is the power of two nearest √N from above, raised to :data:`LANES`
    where that leaves R = N / C ≥ 2 rows, so that both phases keep whole
    lane axes (256×256 at the paper's N = 2¹⁶, 2×128 at N = 2⁸; 8×16 at
    N = 2⁷, where no split has C ≥ 128).
    """
    if N < 4:
        raise ValueError(f"lane_split: N = {N} has no split with R, C >= 2")
    C = 1 << -(-(N.bit_length() - 1) // 2)
    if N >= 2 * LANES:
        C = max(C, LANES)
    return N // C, C


class LaneConsts(NamedTuple):
    """The :class:`FourStepConsts` tables the lane-dense transforms read.

    Arrays only, so a jitted caller traces none of them as a shape: the
    split is read back from ``twiddle.shape``.
    """
    q: np.ndarray                   # (ℓ, 1)
    col_psi_rev: np.ndarray         # (ℓ, R) — R-point fused CT table, root ψ^C
    col_psi_rev_shoup: np.ndarray
    col_psi_inv_rev: np.ndarray     # (ℓ, R) — R-point fused GS table
    col_psi_inv_rev_shoup: np.ndarray
    r_inv: np.ndarray               # (ℓ, 1) — R⁻¹
    r_inv_shoup: np.ndarray
    twiddle: np.ndarray             # (ℓ, R, C) — ψ^{(2k₁+1)n₂}
    twiddle_shoup: np.ndarray
    twiddle_inv: np.ndarray
    twiddle_inv_shoup: np.ndarray
    row_stage: np.ndarray           # (ℓ, C-1) — stage-major ω powers, ω = ψ^{2R}
    row_stage_shoup: np.ndarray
    row_stage_inv: np.ndarray
    row_stage_inv_shoup: np.ndarray
    c_inv: np.ndarray               # (ℓ, 1) — C⁻¹
    c_inv_shoup: np.ndarray


def lane_consts(fc: FourStepConsts) -> LaneConsts:
    """Pick the lane-dense transform's tables out of a four-step table set
    (the same arrays: staging ``fc`` stages these)."""
    return LaneConsts(
        q=fc.q,
        col_psi_rev=fc.col.psi_rev, col_psi_rev_shoup=fc.col.psi_rev_shoup,
        col_psi_inv_rev=fc.col.psi_inv_rev,
        col_psi_inv_rev_shoup=fc.col.psi_inv_rev_shoup,
        r_inv=fc.col.n_inv, r_inv_shoup=fc.col.n_inv_shoup,
        twiddle=fc.twiddle, twiddle_shoup=fc.twiddle_shoup,
        twiddle_inv=fc.twiddle_inv, twiddle_inv_shoup=fc.twiddle_inv_shoup,
        row_stage=fc.row_stage, row_stage_shoup=fc.row_stage_shoup,
        row_stage_inv=fc.row_stage_inv,
        row_stage_inv_shoup=fc.row_stage_inv_shoup,
        c_inv=fc.c_inv, c_inv_shoup=fc.c_inv_shoup)


def _bitrev_rows(x):
    """Bit-reversal permutation of axis -2; the last (lane) axis stays whole."""
    R = x.shape[-2]
    k = R.bit_length() - 1
    if k <= 1:
        return x
    lead = x.shape[:-2]
    nl = len(lead)
    y = x.reshape(*lead, *([2] * k), x.shape[-1])
    perm = (*range(nl), *(nl + k - 1 - i for i in range(k)), nl + k)
    return y.transpose(perm).reshape(x.shape)


def _by_block(tab, m, R):
    """Per-row twiddles of a stage with m blocks: row r reads tab[:, r·m // R]."""
    return jnp.broadcast_to(tab[:, :, None], (tab.shape[0], m, R // m)
                            ).reshape(-1, R)


def _by_offset(tab, t, R):
    """Per-row twiddles of a stage with half-size t: row r reads tab[:, r % t]."""
    return jnp.broadcast_to(tab[:, None, :], (tab.shape[0], R // t, t)
                            ).reshape(-1, R)


# One butterfly on a pair of planes.  Jitted, so that a transform traces
# each of them once per plane shape, not once per pair: the jitted caller
# inlines them, and its trace and lowering (paid at every process start,
# persistent compile cache or not) stay as short with 4 stages per pass as
# with 1.
@jax.jit
def _ct(a, b, w, ws, q):
    """Cooley-Tukey (a + w·b, a − w·b), lazy [0, 2q)."""
    bw = mm.mulmod_shoup_lazy(b, w, ws, q)
    return mm.addmod_lazy(a, bw, q + q), mm.submod_lazy(a, bw, q + q)


@jax.jit
def _gs(a, b, w, ws, q):
    """Gentleman-Sande (a + b, (a − b)·w), lazy [0, 2q)."""
    return (mm.addmod_lazy(a, b, q + q),
            mm.mulmod_shoup_lazy(mm.submod_lazy(a, b, q + q), w, ws, q))


def _row_butterflies(x, stages, q):
    """Radix-2 butterfly stages along axis -2 of (..., ℓ, R, C), lazy [0, 2q).

    ``stages`` lists ``(t, gs, w, ws)``: the partner distance t, the
    butterfly (:func:`_gs` if ``gs``, else :func:`_ct`) and its (ℓ, R)
    per-row twiddles, indexed by the row of the pair's first element.
    :data:`STAGE_GROUP` consecutive stages run on one view of the rows,
    (G, 2, …, 2, t_min), with one write at its end; the lane axis is never
    split.
    """
    R, C = x.shape[-2:]
    lead = x.shape[:-2]
    qb = q[..., None, None]
    for i in range(0, len(stages), STAGE_GROUP):
        group = stages[i:i + STAGE_GROUP]
        k = len(group)
        t_hi = max(s[0] for s in group)
        view = (R // (2 * t_hi), *([2] * k), t_hi >> (k - 1))
        y = x.reshape(*lead, *view, C)
        keys = list(np.ndindex(*([2] * k)))    # one bit per stage of the group
        planes = {p: y[(..., slice(None), *p, slice(None), slice(None))]
                  for p in keys}
        for t, gs, w, ws in group:
            axis = (t_hi // t).bit_length() - 1
            w = w.reshape(-1, *view)
            ws = ws.reshape(-1, *view)
            for lo in keys:
                if lo[axis]:
                    continue
                hi = (*lo[:axis], 1, *lo[axis + 1:])
                at = (slice(None), slice(None), *lo, slice(None))
                wp, wsp = w[at][..., None], ws[at][..., None]
                planes[lo], planes[hi] = (_gs if gs else _ct)(
                    planes[lo], planes[hi], wp, wsp, qb)
        x = jnp.stack([planes[p] for p in keys], axis=-3).reshape(x.shape)
    return x


def ntt_lanes(x, lc: LaneConsts):
    """Forward negacyclic NTT, lane-dense four-step; equal to :func:`ntt`.

    A[n₁, n₂] = a[C·n₁ + n₂] keeps C on the lane axis while the R-point
    negacyclic column NTT runs along the rows (fused CT, bit-reversed
    output, put back in order by a row permutation); the inter-step twiddle
    follows, then one transpose to (C, R), then the C-point cyclic DFT, again
    along the rows (DIF, reordered by a row permutation).  Flattening the
    (k₂, k₁) result gives â[k₁ + R·k₂]: natural order, no lane transpose.
    """
    R, C = lc.twiddle.shape[-2:]
    lead = x.shape[:-1]
    q3 = lc.q[..., None]
    col = [(R // (2 * m), False, _by_block(lc.col_psi_rev[:, m:2 * m], m, R),
            _by_block(lc.col_psi_rev_shoup[:, m:2 * m], m, R))
           for m in (1 << i for i in range(R.bit_length() - 1))]
    row = [(t, True, _by_offset(lc.row_stage[:, t - 1:2 * t - 1], t, C),
            _by_offset(lc.row_stage_shoup[:, t - 1:2 * t - 1], t, C))
           for t in (C >> i for i in range(1, C.bit_length()))]
    A = _row_butterflies(x.reshape(*lead, R, C), col, lc.q)
    A = mm.mulmod_shoup_lazy(_bitrev_rows(A), lc.twiddle, lc.twiddle_shoup, q3)
    A = _row_butterflies(jnp.swapaxes(A, -1, -2), row, lc.q)
    return _bitrev_rows(mm.reduce_once(A, q3)).reshape(*lead, R * C)


def intt_lanes(x, lc: LaneConsts):
    """Inverse of :func:`ntt_lanes`; equal to :func:`intt`.

    Accepts lazy inputs (any values < 2q); output fully reduced in [0, q).
    The mirror of the forward: row permutation and DIT inverse row DFT on
    (C, R), one transpose, the inverse twiddle, row permutation and fused
    GS inverse column NTT, then the C⁻¹·R⁻¹ scaling.
    """
    R, C = lc.twiddle.shape[-2:]
    lead = x.shape[:-1]
    q3 = lc.q[..., None]
    row = [(m, False, _by_offset(lc.row_stage_inv[:, m - 1:2 * m - 1], m, C),
            _by_offset(lc.row_stage_inv_shoup[:, m - 1:2 * m - 1], m, C))
           for m in (1 << i for i in range(C.bit_length() - 1))]
    col = [(R // (2 * h), True, _by_block(lc.col_psi_inv_rev[:, h:2 * h], h, R),
            _by_block(lc.col_psi_inv_rev_shoup[:, h:2 * h], h, R))
           for h in (R >> i for i in range(1, R.bit_length()))]
    B = _row_butterflies(_bitrev_rows(x.reshape(*lead, C, R)), row, lc.q)
    B = mm.mulmod_shoup_lazy(jnp.swapaxes(B, -1, -2), lc.twiddle_inv,
                             lc.twiddle_inv_shoup, q3)
    B = _row_butterflies(_bitrev_rows(B), col, lc.q)
    B = mm.mulmod_shoup_lazy(B, lc.c_inv[..., None], lc.c_inv_shoup[..., None],
                             q3)
    B = mm.mulmod_shoup(B, lc.r_inv[..., None], lc.r_inv_shoup[..., None], q3)
    return B.reshape(*lead, R * C)


# ----------------------------------------------------------------------------
# O(N²) naive oracle (host-side, Python ints) — ground truth for tests
# ----------------------------------------------------------------------------

def naive_ntt(a: np.ndarray, q: int, N: int) -> np.ndarray:
    """â[k] = Σₙ a[n]·ψ^{(2k+1)n} mod q via exact big-int arithmetic."""
    psi = rns.find_psi(q, N)
    out = np.zeros(N, dtype=np.uint32)
    for k in range(N):
        root = pow(psi, 2 * k + 1, q)
        acc, w = 0, 1
        for n in range(N):
            acc = (acc + int(a[n]) * w) % q
            w = w * root % q
        out[k] = acc
    return out
