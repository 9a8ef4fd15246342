"""RNS polynomial container and element-wise ring arithmetic.

An :class:`RnsPoly` holds the (ℓ × N) u32 residue matrix of one element of
R_{Q_ℓ} (paper §II-B): row *i* is the limb mod ``basis[i]``.  ``domain`` is
either ``"coeff"`` (power basis) or ``"ntt"`` (evaluations at ψ^{2k+1},
natural order).  Ciphertexts stack two polys on a leading axis.

All arithmetic is u32-only (see :mod:`repro.core.modmath`); per-limb constants
come device-resident from :mod:`repro.core.const_cache` and are passed to the
jitted ring ops as operands.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import const_cache
from . import guards
from . import modmath as mm
from . import ntt as nttm
from . import rns
from . import spans
from . import trace

COEFF = "coeff"
NTT = "ntt"


def ntt(x, lc: nttm.LaneConsts):
    """The production forward NTT, :func:`~repro.core.ntt.ntt_lanes`."""
    return nttm.ntt_lanes(x, lc)


def intt(x, lc: nttm.LaneConsts):
    """The production inverse NTT, :func:`~repro.core.ntt.intt_lanes`."""
    return nttm.intt_lanes(x, lc)


# Every ring op is ONE compiled program per (op, shape).  Dispatched op by op,
# the u32 chains behind them (≈50 element-wise primitives per mulmod, ≈35 per
# NTT butterfly stage) would each compile and launch on their own.  The NTT
# programs keep the names ``jit_ntt``/``jit_intt`` (the wrappers above).
_ntt, _intt = jax.jit(ntt), jax.jit(intt)
_addmod, _submod = jax.jit(mm.addmod), jax.jit(mm.submod)
_negmod, _mulmod = jax.jit(mm.negmod), jax.jit(mm.mulmod)
mulmod_shoup = jax.jit(mm.mulmod_shoup)


def consts(basis: tuple[int, ...], N: int) -> const_cache.LimbConsts:
    """Per-limb scalar constants, staged to the device once per (basis, N)."""
    return const_cache.device_limb_consts(tuple(basis), N)


def transform_consts(basis: tuple[int, ...], N: int) -> nttm.LaneConsts:
    """The tables of :func:`ntt`/:func:`intt` for (basis, N), staged once."""
    return const_cache.device_lane_consts(tuple(basis), N)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["data"],
    meta_fields=["basis", "domain"],
)
@dataclasses.dataclass
class RnsPoly:
    """(..., ℓ, N) u32 residues. ``basis`` is the tuple of primes, one per limb."""
    data: Any                       # jnp/np array (..., ℓ, N) u32
    basis: tuple[int, ...]
    domain: str

    @property
    def N(self) -> int:
        return self.data.shape[-1]

    @property
    def ell(self) -> int:
        return len(self.basis)

    def c(self) -> const_cache.LimbConsts:
        return consts(self.basis, self.N)

    # -- domain conversion ---------------------------------------------------
    def to_ntt(self) -> "RnsPoly":
        if self.domain == NTT:
            return self
        trace.record("ntt", int(np.prod(self.data.shape[:-1])), self.N)
        from . import distributed as dist  # lazy: distributed imports bconv
        ctx = dist.dist_active()
        if ctx is not None:
            return RnsPoly(dist.sharded_ntt(ctx, self.data, self.basis, True),
                           self.basis, NTT)
        with spans.span("ntt"):
            return RnsPoly(_ntt(self.data, transform_consts(self.basis, self.N)),
                           self.basis, NTT)

    def to_coeff(self) -> "RnsPoly":
        if self.domain == COEFF:
            return self
        trace.record("intt", int(np.prod(self.data.shape[:-1])), self.N)
        from . import distributed as dist
        ctx = dist.dist_active()
        if ctx is not None:
            return RnsPoly(dist.sharded_ntt(ctx, self.data, self.basis, False),
                           self.basis, COEFF)
        with spans.span("intt"):
            return RnsPoly(_intt(self.data, transform_consts(self.basis, self.N)),
                           self.basis, COEFF)

    # -- ring ops (domain-agnostic element-wise; mul requires NTT) -----------
    def _check_aligned(self, o: "RnsPoly", op: str) -> None:
        """Typed basis/domain mismatch (guards on) instead of a bare assert —
        the serving layer quarantines GuardError, an AssertionError would
        take the whole wave down as an engine bug."""
        guards.check_basis_match(self.basis, o.basis, f"RnsPoly.{op}")
        if guards.active() and self.domain != o.domain:
            raise guards.BasisMismatch(
                f"RnsPoly.{op}: domain mismatch {self.domain} vs {o.domain}")

    def __add__(self, o: "RnsPoly") -> "RnsPoly":
        self._check_aligned(o, "add")
        assert self.basis == o.basis and self.domain == o.domain
        return RnsPoly(_addmod(self.data, o.data, self.c().q), self.basis,
                       self.domain)

    def __sub__(self, o: "RnsPoly") -> "RnsPoly":
        self._check_aligned(o, "sub")
        assert self.basis == o.basis and self.domain == o.domain
        return RnsPoly(_submod(self.data, o.data, self.c().q), self.basis,
                       self.domain)

    def __neg__(self) -> "RnsPoly":
        return RnsPoly(_negmod(self.data, self.c().q), self.basis,
                       self.domain)

    def __mul__(self, o: "RnsPoly") -> "RnsPoly":
        self._check_aligned(o, "mul")
        assert self.basis == o.basis
        assert self.domain == NTT and o.domain == NTT, "mul requires NTT domain"
        c = self.c()
        trace.record("elt_mul", int(np.prod(self.data.shape[:-1])), self.N)
        return RnsPoly(_mulmod(self.data, o.data, c.q, c.qinv_neg, c.r2),
                       self.basis, NTT)

    def mul_scalar(self, scalars: np.ndarray) -> "RnsPoly":
        """Multiply limb i by the constant ``scalars[i]`` (Shoup).

        The per-limb Shoup companions are built host-side once per
        (basis, scalars) — rescale/ModDown reuse the same vector every call —
        and staged device-resident through the constant cache.
        """
        c = self.c()
        sv = np.asarray(scalars, dtype=np.uint32).reshape(-1)

        def build():
            w = sv.reshape(-1, 1)
            ws = np.array([[rns.shoup(int(v), q)] for v, q in zip(sv, self.basis)],
                          dtype=np.uint32)
            return w, ws

        w, ws = const_cache.device_table(("mul_scalar", self.basis, sv.tobytes()),
                                         build)
        return RnsPoly(mulmod_shoup(self.data, w, ws, c.q),
                       self.basis, self.domain)

    # -- structure ------------------------------------------------------------
    def limbs(self, idx: slice) -> "RnsPoly":
        """Sub-poly restricted to a contiguous slice of limbs."""
        return RnsPoly(self.data[..., idx, :], self.basis[idx], self.domain)

    def automorphism(self, perm) -> "RnsPoly":
        """Apply φ as an NTT-domain index permutation (natural order).

        ``perm`` may be a host numpy vector or an already-staged device array
        (``jnp.asarray`` is a no-op for the latter — zero uploads).  Natural
        order ONLY: under an active ``dist_scope`` the data lives in the
        four-step NTT layout, so callers must go through
        :meth:`automorphism_by_gelt`, which conjugates the perm by the
        layout and shards the gather.
        """
        assert self.domain == NTT
        trace.record("auto", int(np.prod(self.data.shape[:-1])), self.N)
        return RnsPoly(jnp.take(self.data, jnp.asarray(perm), axis=-1),
                       self.basis, NTT)

    def automorphism_by_gelt(self, g: int) -> "RnsPoly":
        """φ_g via the device-staged perm table from ``const_cache`` — the
        steady-state rotation path performs zero per-call perm uploads."""
        from . import distributed as dist
        ctx = dist.dist_active()
        if ctx is not None:
            assert self.domain == NTT
            trace.record("auto", int(np.prod(self.data.shape[:-1])), self.N)
            return RnsPoly(dist.sharded_galois(ctx, self.data, self.N, g),
                           self.basis, NTT)
        return self.automorphism(const_cache.device_galois_perm(self.N, g))


# ----------------------------------------------------------------------------
# Automorphism index maps (paper §II-C) — natural-order NTT domain.
# ----------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def automorphism_perm(N: int, g: int) -> np.ndarray:
    """perm[k] = k' s.t. (φ_g m)(ψ^{2k+1}) = m̂[k'], i.e. 2k'+1 = (2k+1)·g mod 2N."""
    k = np.arange(N, dtype=np.int64)
    return ((((2 * k + 1) * g) % (2 * N) - 1) // 2).astype(np.int32)


@functools.lru_cache(maxsize=None)
def automorphism_perm_coeff(N: int, g: int) -> tuple[np.ndarray, np.ndarray]:
    """Coefficient-domain map: X^j → ±X^{j·g mod N}; returns (dst index, sign flip)."""
    j = np.arange(N, dtype=np.int64)
    t = (j * g) % (2 * N)
    return (t % N).astype(np.int32), (t >= N)


def galois_elt(r: int, N: int) -> int:
    """Galois element for slot rotation by r (5^r mod 2N); r may be negative."""
    M = 2 * N
    return pow(5, r % (N // 2), M)


CONJ_GELT = -1  # sentinel: conjugation uses g = 2N - 1


def apply_automorphism_coeff(data: np.ndarray, N: int, g: int,
                             q: np.ndarray) -> np.ndarray:
    """Host-side coefficient-domain automorphism with negacyclic signs."""
    dst, flip = automorphism_perm_coeff(N, g)
    out = np.zeros_like(data)
    vals = np.where(flip, (q.reshape(-1, 1) - data) % q.reshape(-1, 1), data)
    out[..., dst] = vals
    return out


# ----------------------------------------------------------------------------
# Sampling (host-side numpy; keys and encryption randomness)
# ----------------------------------------------------------------------------

def uniform_poly(rng: np.random.Generator, basis: tuple[int, ...], N: int,
                 domain: str = NTT) -> RnsPoly:
    data = np.stack([rng.integers(0, q, N, dtype=np.int64).astype(np.uint32)
                     for q in basis])
    return RnsPoly(jnp.asarray(data), basis, domain)


def small_to_rns(small: np.ndarray, basis: tuple[int, ...]) -> np.ndarray:
    """Signed small integer vector → (ℓ, N) residues."""
    return np.stack([(small.astype(np.int64) % q).astype(np.uint32) for q in basis])


def gaussian_poly(rng: np.random.Generator, basis: tuple[int, ...], N: int,
                  sigma: float = 3.2) -> RnsPoly:
    e = np.round(rng.normal(0.0, sigma, N)).astype(np.int64)
    return RnsPoly(jnp.asarray(small_to_rns(e, basis)), basis, COEFF)


def ternary_secret(rng: np.random.Generator, N: int,
                   hamming: int | None = None) -> np.ndarray:
    """Ternary secret in {-1, 0, 1}^N.

    ``hamming=None`` → uniform ternary (non-sparse keys, paper Table I [11]);
    otherwise exactly ``hamming`` nonzeros (sparse secrets for bootstrapping's
    EvalMod range, as in the sparse-secret-encapsulation of [12]).
    """
    if hamming is None:
        return rng.integers(-1, 2, N, dtype=np.int64).astype(np.int8)
    s = np.zeros(N, dtype=np.int8)
    idx = rng.choice(N, size=hamming, replace=False)
    s[idx] = rng.choice(np.array([-1, 1], dtype=np.int8), size=hamming)
    return s
