"""AutoU: automorphism φ_g as an NTT-domain index permutation, plus the fused
AutoU∘KS MAC kernel.

CiFHER's AutoU is a permutation network over the lanes.  In the
natural-order NTT domain φ_g is sign-free (see
``repro.core.poly.automorphism_perm``), but it moves every coefficient to an
arbitrary lane of the length-N row, and Mosaic has no gather across lanes.
So the permutation runs as an XLA gather on the chip, and Pallas keeps the
arithmetic:

* :func:`automorphism` — one permutation over all leading dims (ciphertext
  components × limbs), one dispatch.
* :func:`automorphism_many` — R *different* permutations in one dispatch;
  the data operand either provides one block per permutation (G = R) or is
  shared by all of them (G = 1, broadcast).  This batches the b-halves of a
  rotation set.
* :func:`auto_ks_pallas` — AutoU∘KS for a whole rotation set in one jitted
  program: the gather permutes each hoisted digit, then a Pallas kernel does
  the evk MAC.  One program owns one (rotation, limb-block, coefficient-tile)
  output block and loops digits in VREGs (output-stationary, like the BConvU
  kernel); products use double-REDC Montgomery (evk halves are data, not
  constants — no Shoup companions), accumulation is the lazy hi16/lo16
  column sum with a single Barrett reduction per output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import modmath as mm
from repro.kernels.config import effective_block, lane_tile

_M16 = 0xFFFF  # Python int: weak-typed, safe inside Pallas kernels


def _permute(x, perm):
    return jnp.take(x, perm, axis=-1, mode="clip")


@jax.jit
def automorphism(x, perm):
    """x: (..., N) u32, perm: (N,) i32 → out[..., k] = x[..., perm[k]]."""
    return _permute(x, perm)


@jax.jit
def automorphism_many(x, perms):
    """x: (G, L, N) with G ∈ {1, R}; perms: (R, N) → out (R, L, N).

    out[r, i, k] = x[r if G == R else 0, i, perms[r, k]] — R different Galois
    permutations in ONE dispatch; G = 1 broadcasts a shared operand (e.g. the
    b-half of a hoisted rotation set) across all R permutations.
    """
    G, R = x.shape[0], perms.shape[0]
    assert G in (1, R), f"data batch {G} must be 1 or match perms batch {R}"
    if G == 1:
        return jax.vmap(lambda p: _permute(x[0], p))(perms)
    return jax.vmap(_permute)(x, perms)


# ----------------------------------------------------------------------------
# Fused AutoU ∘ KS
# ----------------------------------------------------------------------------

def _auto_ks_body(J, ext_ref, ea_ref, eb_ref,
                  q_ref, qinv_ref, r2_ref, muh_ref, mul_ref, o_ref):
    q, qinv, r2 = q_ref[...], qinv_ref[...], r2_ref[...]   # (Lb, 1) columns
    acc = [None] * 4                          # lo_a, hi_a, lo_b, hi_b
    for j in range(J):                        # static digit contraction
        e = ext_ref[0, j]
        ta = mm.mulmod(e, ea_ref[0, j], q, qinv, r2)
        tb = mm.mulmod(e, eb_ref[0, j], q, qinv, r2)
        for n, part in enumerate((ta & _M16, ta >> 16, tb & _M16, tb >> 16)):
            acc[n] = part if acc[n] is None else acc[n] + part
    for c in range(2):
        lo16, hi16 = acc[2 * c], acc[2 * c + 1]
        lo = ((hi16 & _M16) << 16) + lo16
        carry = (lo < lo16).astype(jnp.uint32)
        hi = (hi16 >> 16) + carry
        o_ref[0, c] = mm.barrett_reduce_wide(hi, lo, q, muh_ref[...],
                                             mul_ref[...])


@functools.partial(jax.jit, static_argnames=("limbs_per_block", "interpret"))
def auto_ks_pallas(exts, evk_a, evk_b, perms, q, qinv_neg, r2, mu_hi, mu_lo,
                   *, limbs_per_block: int | None = None,
                   interpret: bool = True):
    """φ_g ∘ (evk inner product) for R rotations in ONE dispatch.

        out[r, 0, i, k] = Σ_j exts[j, ·, i, perms[r, k]] · evk_a[r, j, i, k]
        out[r, 1, i, k] = Σ_j exts[j, ·, i, perms[r, k]] · evk_b[r, j, i, k]

    ``exts``: (J, G, L, N) hoisted digit decompositions with G ∈ {1, R} —
    G = 1 shares one ModUp across all rotations (hoisting), G = R gives each
    rotation its own decomposition (batched distinct ciphertexts).
    ``evk_a``/``evk_b``: (R, J, L, N) level-sliced digit keys; ``perms``:
    (R, N) i32; per-limb consts (L, 1).  Kernel grid = (R, L/limbs_per_block,
    N/tile), each program output-stationary over its block.
    """
    J, G, L, N = exts.shape
    R = perms.shape[0]
    assert G in (1, R), f"exts batch {G} must be 1 or match perms batch {R}"
    assert evk_a.shape == (R, J, L, N) and evk_b.shape == (R, J, L, N)
    # (R, J, L, N): digit j of rotation r, permuted by φ_{g_r}
    if G == 1:
        rot = jax.vmap(lambda p: _permute(exts[:, 0], p))(perms)
    else:
        rot = jax.vmap(_permute, in_axes=(1, 0))(exts, perms)
    Lb = effective_block(L, limbs_per_block, tiled=True)
    tile = lane_tile(N, J * (-(-Lb // 8) * 8))
    data_spec = pl.BlockSpec((1, J, Lb, tile), lambda r, l, c: (r, 0, l, c))
    const_spec = pl.BlockSpec((Lb, 1), lambda r, l, c: (l, 0))
    return pl.pallas_call(
        functools.partial(_auto_ks_body, J),
        grid=(R, L // Lb, N // tile),
        in_specs=[data_spec, data_spec, data_spec] + [const_spec] * 5,
        out_specs=pl.BlockSpec((1, 2, Lb, tile), lambda r, l, c: (r, 0, l, c)),
        out_shape=jax.ShapeDtypeStruct((R, 2, L, N), jnp.uint32),
        interpret=interpret,
        name="auto_ks_mac",
    )(rot, evk_a, evk_b, q, qinv_neg, r2, mu_hi, mu_lo)
