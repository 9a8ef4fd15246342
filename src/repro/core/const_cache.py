"""Device-resident constant cache for NTT/four-step/scalar tables.

The CKKS layer runs the pure-``jnp`` path eagerly (un-jitted), so every
``jnp.asarray(numpy_table)`` inside a transform used to stage the table to the
device again on *every call*.  This module stages each constant set exactly
once per key — ``(basis, N)`` for :class:`~repro.core.ntt.NttConsts` and
:class:`LimbConsts`,
``(basis, N, R)`` for :class:`~repro.core.ntt.FourStepConsts`, and an explicit
key for ad-hoc scalar vectors — and hands back the same jax-array pytree on
every subsequent lookup.  Under ``jit`` the arrays are already committed
device buffers, so tracing embeds them without a host round-trip either.

Host-side table *generation* stays in :mod:`repro.core.rns` /
:mod:`repro.core.ntt` (numpy + Python ints, lru-cached); this cache is purely
the numpy → device staging layer.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Hashable, NamedTuple

import jax.numpy as jnp
import numpy as np

from . import ntt as nttm
from . import rns


class ConstCache:
    """Tiny keyed staging cache: builder() runs once per key.

    Bounded: once ``max_entries`` is reached the oldest entry is evicted
    (insertion order).  The named constant families (NTT tables, rescale
    q⁻¹, ModDown P⁻¹, …) are few per parameter set, but ``mul_const``-style
    callers key on runtime scalar *values*, which would otherwise grow the
    store — and pin device buffers — without bound in a long-running server.
    """

    def __init__(self, max_entries: int = 4096) -> None:
        self._store: dict[Hashable, Any] = {}
        self.max_entries = max_entries

    def get(self, key: Hashable, builder: Callable[[], Any]) -> Any:
        out = self._store.get(key)
        if out is None:
            out = builder()
            if len(self._store) >= self.max_entries:
                self._store.pop(next(iter(self._store)))
            self._store[key] = out
        return out

    def clear(self) -> None:
        self._store.clear()

    def __len__(self) -> int:
        return len(self._store)


_cache = ConstCache()


def clear() -> None:
    """Drop ALL staged constants (tests / device resets) — the ad-hoc table
    store and the lru-cached device NttConsts/FourStepConsts alike."""
    _cache.clear()
    device_ntt_consts.cache_clear()
    device_limb_consts.cache_clear()
    device_four_step_consts.cache_clear()
    device_lane_consts.cache_clear()
    device_bconv_consts.cache_clear()


_stage_events = 0

# Optional pre-staging hook: called as hook(n) before a host→device constant
# transfer is counted.  The fault-injection framework (repro.runtime.faults)
# installs a callback here that may raise StagingFault, modeling a failed
# upload of tables / evk material.  None (the default) is free.
_stage_hook = None


def set_stage_hook(fn) -> None:
    """Install (or clear, with None) the pre-staging fault hook."""
    global _stage_hook
    _stage_hook = fn


def get_stage_hook():
    """The currently-installed pre-staging hook (None when clear) — read by
    consumers that chain through and restore it (fault injection, tracing)."""
    return _stage_hook


def stage_events() -> int:
    """Monotonic count of host→device constant staging transfers.

    Every ``jnp.asarray(numpy_table)`` issued by this module bumps the
    counter, so benchmarks/tests can assert the steady-state path performs
    ZERO per-call table uploads (``BENCH_bconv.json``'s upload gate): snapshot
    before, run the hot loop, assert the delta is 0.
    """
    return _stage_events


def record_stage(n: int = 1) -> None:
    """Count ``n`` externally-performed host→device staging transfers.

    Device-resident state that is staged *outside* this module's builders —
    the serve keystore's evk digit-key stacks, for instance — reports its
    uploads here so ``stage_events()`` stays the single steady-state-upload
    metric every bench gate reads.
    """
    global _stage_events
    if _stage_hook is not None:
        _stage_hook(n)
    _stage_events += n


def stage_events_since(snapshot: int) -> int:
    """Uploads since a ``stage_events()`` snapshot."""
    return _stage_events - snapshot


def _stage(x):
    global _stage_events
    if isinstance(x, np.ndarray):
        if _stage_hook is not None:
            _stage_hook(1)
        _stage_events += 1
        return jnp.asarray(x)
    return x


@functools.lru_cache(maxsize=None)
def device_ntt_consts(basis: tuple[int, ...], N: int) -> nttm.NttConsts:
    """Stacked NTT constants as device-resident jax arrays, staged once."""
    c = nttm.stacked_ntt_consts(basis, N)
    return nttm.NttConsts(*(_stage(f) for f in c))


class LimbConsts(NamedTuple):
    """The per-limb scalar columns of :class:`~repro.core.ntt.NttConsts`,
    each (ℓ, 1): all that element-wise ring ops and the eltwise / auto_ks
    kernels read, without the (ℓ, N) ψ tables no production transform
    reads."""
    q: Any
    qinv_neg: Any
    r2: Any
    mu_hi: Any
    mu_lo: Any


@functools.lru_cache(maxsize=None)
def device_limb_consts(basis: tuple[int, ...], N: int) -> LimbConsts:
    """Per-limb scalar constants as device-resident jax arrays, staged once."""
    tabs = [rns.prime_tables(q, N) for q in basis]
    return LimbConsts(*(
        _stage(np.array([[getattr(t, f)] for t in tabs], dtype=np.uint32))
        for f in LimbConsts._fields))


@functools.lru_cache(maxsize=None)
def device_four_step_consts(basis: tuple[int, ...], N: int,
                            R: int) -> nttm.FourStepConsts:
    """Stacked four-step constants as device-resident jax arrays, staged once."""
    fc = nttm.stacked_four_step_consts(basis, N, R)
    col = nttm.NttConsts(*(_stage(f) for f in fc.col))
    return fc._replace(
        col=col,
        **{name: _stage(getattr(fc, name))
           for name in fc._fields if name not in ("R", "C", "col")})


@functools.lru_cache(maxsize=None)
def device_lane_consts(basis: tuple[int, ...], N: int) -> nttm.LaneConsts:
    """The lane-dense NTT's tables: the device-resident four-step tables of
    :func:`~repro.core.ntt.lane_split`'s split, staged once."""
    R, _ = nttm.lane_split(N)
    return nttm.lane_consts(device_four_step_consts(basis, N, R))


class BConvConsts(NamedTuple):
    """Device-resident constants for one {src}→{dst} base conversion.

    Shapes are pre-broadcast for both the jnp path and the Pallas BConvU
    kernel: column vectors align with the limb axis of an (…, ℓ, N) operand.
    """
    q_src: jnp.ndarray           # (ℓ, 1) u32 — source primes
    qhat_inv: jnp.ndarray        # (ℓ, 1) — (Q/q_i)⁻¹ mod q_i
    qhat_inv_shoup: jnp.ndarray  # (ℓ, 1)
    table: jnp.ndarray           # (K, ℓ) — Q/q_i mod p_j
    table_shoup: jnp.ndarray     # (K, ℓ)
    q_dst: jnp.ndarray           # (K, 1) — destination primes
    mu_hi: jnp.ndarray           # (K, 1) — Barrett floor(2⁶²/p) split
    mu_lo: jnp.ndarray           # (K, 1)


@functools.lru_cache(maxsize=None)
def device_bconv_consts(src: tuple[int, ...],
                        dst: tuple[int, ...]) -> BConvConsts:
    """BConv tables + per-dst Barrett constants, staged once per (src, dst).

    The Barrett split is derived directly from the dst primes (cheap Python
    ints) rather than through ``prime_tables`` — BConv destinations need no
    NTT-friendliness and no ψ table build.
    """
    tab = rns.bconv_tables(src, dst)
    mu = [(1 << 62) // p for p in dst]
    col = lambda vals: _stage(np.array(vals, dtype=np.uint32).reshape(-1, 1))
    return BConvConsts(
        q_src=col(src),
        qhat_inv=_stage(tab.qhat_inv.reshape(-1, 1)),
        qhat_inv_shoup=_stage(tab.qhat_inv_shoup.reshape(-1, 1)),
        table=_stage(tab.table),
        table_shoup=_stage(tab.table_shoup),
        q_dst=col(dst),
        mu_hi=col([m >> 32 for m in mu]),
        mu_lo=col([m & 0xFFFFFFFF for m in mu]),
    )


def device_galois_perm(N: int, g: int) -> jnp.ndarray:
    """Automorphism index vector perm_{N,g} as a device-resident (N,) i32.

    The host build (:func:`repro.core.poly.automorphism_perm`) is lru-cached
    numpy; this stages it once per (N, g) so rotation-heavy workloads
    (bootstrap fires hundreds per ``linear_transform``) perform ZERO per-call
    perm uploads in steady state — counted by :func:`stage_events` and gated
    in ``BENCH_rotation.json``.
    """
    def build():
        from . import poly
        return poly.automorphism_perm(N, g)
    return device_table(("galois_perm", N, g), build)


def device_galois_perm_stack(N: int, gs: tuple) -> jnp.ndarray:
    """Stacked (R, N) i32 perm table for a rotation *set* — the operand of the
    multi-perm / fused AutoU∘KS kernels, staged once per (N, gs)."""
    def build():
        from . import poly
        return np.stack([poly.automorphism_perm(N, g) for g in gs])
    return device_table(("galois_perm_stack", N, tuple(gs)), build)


def device_table(key: Hashable, builder: Callable[[], Any]) -> Any:
    """Stage an ad-hoc constant (scalar vector, monomial table, …) once.

    ``builder`` returns a numpy array or a tuple of numpy arrays; the staged
    jax-array counterpart is cached under ``key``.
    """
    def stage():
        out = builder()
        if isinstance(out, tuple):
            return tuple(_stage(o) for o in out)
        return _stage(out)
    return _cache.get(key, stage)
