"""Fused element-wise EFU kernel (paper §III-C "compound element-wise ops").

The EFU op menu mirrors CiFHER's: modular mul, add, sub, and the two compound
forms that cut RF (here: HBM↔VMEM) round-trips on the HMult hot path:

    mul      : a ⊙ b
    add/sub  : a ± b
    mac      : a ⊙ b + c ⊙ d            (HMult's d₁ = a₁b₂ + a₂b₁, one pass)
    muladd   : a ⊙ b + c

General products use double-REDC Montgomery (no precomputed companions).

Batched grid (mirroring the NTT/BConv grids): all leading dims of the
operands flatten with the limb axis into ONE grid dimension of
``P · (ℓ / limbs_per_block)`` programs × a coefficient-tile dimension — a
stacked HMult tensor product (both ciphertext components × ℓ limbs) is one
launch instead of one per limb.  The limb block is the sublane dimension of
every block (8-aligned or all ℓ limbs, :func:`~repro.kernels.config.
effective_block`), the per-limb constants ride along as (block, 1) columns,
and the coefficient tile shrinks with the block
(:func:`~repro.kernels.config.lane_tile`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import modmath as mm
from repro.kernels.config import effective_block, lane_tile

OPS = ("mul", "add", "sub", "mac", "muladd")


def _body(op, q_ref, qinv_ref, r2_ref, *refs):
    o_ref = refs[-1]
    ins = [r[0] for r in refs[:-1]]           # (L, tile) blocks
    q, qinv, r2 = q_ref[...], qinv_ref[...], r2_ref[...]   # (L, 1)
    if op == "mul":
        o_ref[0] = mm.mulmod(ins[0], ins[1], q, qinv, r2)
    elif op == "add":
        o_ref[0] = mm.addmod(ins[0], ins[1], q)
    elif op == "sub":
        o_ref[0] = mm.submod(ins[0], ins[1], q)
    elif op == "mac":
        t1 = mm.mulmod(ins[0], ins[1], q, qinv, r2)
        t2 = mm.mulmod(ins[2], ins[3], q, qinv, r2)
        o_ref[0] = mm.addmod(t1, t2, q)
    elif op == "muladd":
        t = mm.mulmod(ins[0], ins[1], q, qinv, r2)
        o_ref[0] = mm.addmod(t, ins[2], q)
    else:  # pragma: no cover
        raise ValueError(op)


@functools.partial(jax.jit, static_argnames=("op", "tile", "limbs_per_block",
                                             "interpret"))
def eltwise_pallas(op: str, q, qinv_neg, r2, *arrays,
                   tile: int = 4096, limbs_per_block: int | None = None,
                   interpret: bool = True):
    """arrays: n× (..., ℓ, N) u32 operands (equal shapes); per-limb consts
    (ℓ, 1).  Leading dims batch into the grid; output shape == input shape."""
    assert op in OPS
    shape = arrays[0].shape
    ell, N = shape[-2], shape[-1]
    flat = [a.reshape(-1, ell, N) for a in arrays]
    P = flat[0].shape[0]
    L = effective_block(ell, limbs_per_block, tiled=True)
    tile = lane_tile(N, L, tile)
    nblk = ell // L
    const_spec = pl.BlockSpec((L, 1), lambda g, c: (g % nblk, 0))
    arr_spec = pl.BlockSpec((1, L, tile), lambda g, c: (g // nblk, g % nblk, c))
    out = pl.pallas_call(
        functools.partial(_body, op),
        grid=(P * nblk, N // tile),
        in_specs=[const_spec] * 3 + [arr_spec] * len(flat),
        out_specs=arr_spec,
        out_shape=jax.ShapeDtypeStruct((P, ell, N), jnp.uint32),
        interpret=interpret,
        name=f"eltwise_{op}",
    )(q, qinv_neg, r2, *flat)
    return out.reshape(shape)
