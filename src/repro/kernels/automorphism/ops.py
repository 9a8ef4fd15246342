"""Public wrappers: rotate/conjugate NTT-domain polys and the fused AutoU∘KS.

Perm tables are device-resident via :mod:`repro.core.const_cache` (staged once
per (N, g) — zero per-call uploads).  The permutations are XLA gathers, which
have no interpret mode: they book as compiled launches of the
``automorphism`` family.  :func:`auto_ks` resolves its execution mode
through :mod:`repro.kernels.config` (``REPRO_KERNEL_MODE``) and its unpinned
launch knobs through the autotuned config cache
(:func:`repro.kernels.autotune.best_config`), like every kernel family.
"""
from __future__ import annotations

from repro.core import const_cache
from repro.core import poly as pl_core
from repro.kernels import autotune, config

from .kernel import auto_ks_pallas, automorphism, automorphism_many


def apply_galois(x, N: int, g: int):
    """x: (..., N) u32 → φ_g(x), batched over all leading dims in one launch."""
    perm = const_cache.device_galois_perm(N, g)
    config.count_launch("automorphism", interpret=False)
    return automorphism(x, perm)


def apply_rotation(x, N: int, r: int):
    return apply_galois(x, N, pl_core.galois_elt(r, N))


def apply_galois_many(x, N: int, gs: tuple):
    """x: (G, L, N) with G ∈ {1, len(gs)} → (R, L, N), one launch for the
    whole rotation set (G = 1 broadcasts a shared operand)."""
    perms = const_cache.device_galois_perm_stack(N, tuple(gs))
    config.count_launch("automorphism", interpret=False)
    return automorphism_many(x, perms)


def auto_ks(exts, evk_a, evk_b, N: int, gs: tuple, basis: tuple[int, ...],
            interpret: bool | None = None,
            limbs_per_block: int | None = None):
    """Fused φ_g ∘ evk-MAC for the rotation set ``gs`` (see
    :func:`repro.kernels.automorphism.kernel.auto_ks_pallas`).

    ``basis`` is the extended basis Q_ℓ ∪ P of the hoisted digits; all limb
    constants (q, Montgomery, Barrett) come device-resident from
    :func:`repro.core.const_cache.device_limb_consts`.
    """
    if limbs_per_block is None:
        limbs_per_block = autotune.best_config(
            "auto_ks", N, exts.shape[-2])["limbs_per_block"]
    c = const_cache.device_limb_consts(tuple(basis), N)
    perms = const_cache.device_galois_perm_stack(N, tuple(gs))
    interp = config.resolve_interpret(interpret)
    config.count_launch("auto_ks", interpret=interp)
    return auto_ks_pallas(exts, evk_a, evk_b, perms,
                          c.q, c.qinv_neg, c.r2, c.mu_hi, c.mu_lo,
                          limbs_per_block=limbs_per_block, interpret=interp)
