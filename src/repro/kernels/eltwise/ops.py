"""Public wrappers for the EFU kernel.

Per-limb constants come device-resident from
:func:`repro.core.const_cache.device_limb_consts` (staged once per (basis, N) —
no per-call uploads), the execution mode resolves through
:mod:`repro.kernels.config`, and unpinned launch knobs (``tile``,
``limbs_per_block``) resolve through the autotuned config cache
(:func:`repro.kernels.autotune.best_config`; cold cache → tile=4096,
limbs_per_block=4).
"""
from __future__ import annotations

from repro.core import const_cache
from repro.kernels import autotune, config

from .kernel import eltwise_pallas


def eltwise(op: str, basis: tuple[int, ...], *arrays,
            interpret: bool | None = None, tile: int | None = None,
            limbs_per_block: int | None = None):
    N = arrays[0].shape[-1]
    if tile is None or limbs_per_block is None:
        cfg = autotune.best_config("eltwise", N, len(basis))
        if tile is None:
            tile = cfg.get("tile")
        if limbs_per_block is None:
            limbs_per_block = cfg.get("limbs_per_block")
    c = const_cache.device_limb_consts(tuple(basis), N)
    interp = config.resolve_interpret(interpret)
    config.count_launch("eltwise", interpret=interp)
    return eltwise_pallas(op, c.q, c.qinv_neg, c.r2, *arrays, tile=tile,
                          limbs_per_block=limbs_per_block,
                          interpret=interp)
