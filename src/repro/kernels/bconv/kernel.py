"""Output-stationary BConv MAC kernel — CiFHER's systolic BConvU on TPU.

The (K×ℓ)·(ℓ×N) modular matrix product (96 % of BConv, paper §II-C) is tiled

    grid  = (B / BLOCK_B, K / BLOCK_K, N / TILE)  # batch × dst primes × coeffs
    x     : BlockSpec (BLOCK_B, ℓ, TILE)    # all source limbs of the tile
    table : BlockSpec (ℓ, BLOCK_K, 1)       # the dst-prime block's columns
    out   : BlockSpec (BLOCK_B, BLOCK_K, TILE)

Each program is *output-stationary*: it owns one (BLOCK_K, TILE) output block
and loops the contraction (ℓ source limbs) in VREGs — the software analogue
of CiFHER's output-stationary MAC array (§III-A).  Source limb i is one row
broadcast over the dst-prime sublanes; its table column is a (BLOCK_K, 1)
column broadcast over the lanes.  Accumulation is **lazy**: per-term Shoup
products reduced to [0,q) are split into hi16/lo16 columns summed in u32
(exact for ℓ < 2¹⁶), with a single Barrett reduction at the end — one
reduction per output instead of one per MAC.

The leading ``B`` axis batches every independent BConv operand the caller has
in flight — ciphertext components, stacked key-switching accumulators, digit
polys of equal basis — into ONE grid launch, the dispatch-amortization
analogue of the NTT kernel's flattened limb grid.  ``block_b`` groups several
batch elements per program (table columns and Barrett constants are loaded
once per program, reused across the block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import modmath as mm
from repro.kernels.config import effective_block, lane_tile


def effective_block_b(B: int, requested: int | None) -> int:
    """Largest divisor of ``B`` that is ≤ the requested batch block (default 4)."""
    return effective_block(B, requested)


def _body(ell, block_b, x_ref, tab_ref, tabs_ref, q_ref, mu_hi_ref, mu_lo_ref,
          o_ref):
    q = q_ref[...]                            # (BLOCK_K, 1) columns
    mu_hi, mu_lo = mu_hi_ref[...], mu_lo_ref[...]
    for b in range(block_b):                  # static batch block
        lo16 = hi16 = None
        for i in range(ell):                  # static contraction loop
            term = mm.mulmod_shoup(x_ref[b, pl.ds(i, 1), :], tab_ref[i],
                                   tabs_ref[i], q)
            lo, hi = term & 0xFFFF, term >> 16
            lo16 = lo if lo16 is None else lo16 + lo
            hi16 = hi if hi16 is None else hi16 + hi
        lo = ((hi16 & 0xFFFF) << 16) + lo16
        carry = (lo < lo16).astype(jnp.uint32)
        hi = (hi16 >> 16) + carry
        o_ref[b] = mm.barrett_reduce_wide(hi, lo, q, mu_hi, mu_lo)


@functools.partial(jax.jit, static_argnames=("tile", "block_b", "interpret"))
def bconv_matmul_pallas(t, table, table_shoup, q_dst, mu_hi, mu_lo,
                        *, tile: int | None = None, block_b: int | None = None,
                        interpret: bool = True):
    """t: (B, ℓ, N) or (ℓ, N) pre-scaled source limbs; table: (K, ℓ) → out
    (B, K, N) (resp. (K, N)).

    ``q_dst``/``mu_*``: (K, 1) per-dst-prime constants.  ``block_b`` batch
    elements share one grid program (rounded down to a divisor of B); the
    dst primes split into 8-aligned blocks where K allows.
    """
    squeeze = t.ndim == 2
    if squeeze:
        t = t[None]
    B, ell, N = t.shape
    K = table.shape[0]
    bb = effective_block_b(B, block_b)
    kb = effective_block(K, 8, tiled=True)
    tile = lane_tile(N, bb * max(-(-ell // 8) * 8, kb), tile)
    # (K, ℓ) → (ℓ, K, 1): source limb i's column is the (kb, 1) block [i]
    cols = lambda tab: tab.T[:, :, None]
    const_spec = pl.BlockSpec((kb, 1), lambda b, j, c: (j, 0))
    tab_spec = pl.BlockSpec((ell, kb, 1), lambda b, j, c: (0, j, 0))
    out = pl.pallas_call(
        functools.partial(_body, ell, bb),
        grid=(B // bb, K // kb, N // tile),
        in_specs=[
            pl.BlockSpec((bb, ell, tile), lambda b, j, c: (b, 0, c)),
            tab_spec, tab_spec, const_spec, const_spec, const_spec,
        ],
        out_specs=pl.BlockSpec((bb, kb, tile), lambda b, j, c: (b, j, c)),
        out_shape=jax.ShapeDtypeStruct((B, K, N), jnp.uint32),
        interpret=interpret,
        name="bconv_matmul",
    )(t, cols(table), cols(table_shoup), q_dst, mu_hi, mu_lo)
    return out[0] if squeeze else out
