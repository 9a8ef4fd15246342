"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU v5e.

Each case lowers one kernel with ``interpret=False`` at the paper's width
(N = 2¹⁶, up to 60 limbs) against a described, unattached v5e chip and
compiles it with the TPU compiler that ships with jax — no chip needed.  A
kernel that passes every interpret-mode test can still be refused here (block
shapes off the (8, 128) tiling, fast-memory overruns, unsupported in-kernel
ops); this file is where such a regression shows up before any chip run.

The topology is described inside a module-scoped fixture, never at import:
only the worker that runs this file loads the TPU library.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

N = 1 << 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one — keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", saved)
    cc.reset_cache()


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return compiled


@pytest.mark.parametrize("op,lead,ell", [
    ("mul", (2,), 60),          # HMult's stacked (d₀, d₂) products
    ("mac", (), 60),            # HMult's d₁ = a₁b₂ + a₂b₁
    ("mul", (2, 4), 47),        # a wave of 4, odd limb count after rescale
])
def test_eltwise_compiles_for_v5e(one_chip, no_persistent_cache, op, lead,
                                  ell):
    from repro.kernels.eltwise.kernel import eltwise_pallas
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.uint32,
                                           sharding=one_chip)
    n_in = 4 if op == "mac" else 2
    fn = lambda q, qi, r2, *xs: eltwise_pallas(op, q, qi, r2, *xs,
                                               interpret=False)
    _compile(fn, S((ell, 1)), S((ell, 1)), S((ell, 1)),
             *[S(lead + (ell, N))] * n_in)


@pytest.mark.parametrize("B,ell,K", [
    (8, 12, 48),                # ModUp of a digit, a wave of 4 × (a, b)
    (2, 12, 47),                # ModUp into an odd remainder basis
    (4, 12, 60),                # a digit into the full Q ∪ P basis
])
def test_bconv_compiles_for_v5e(one_chip, no_persistent_cache, B, ell, K):
    from repro.kernels.bconv.kernel import bconv_matmul_pallas
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.uint32,
                                           sharding=one_chip)
    fn = lambda t, tab, tabs, q, mh, ml: bconv_matmul_pallas(
        t, tab, tabs, q, mh, ml, interpret=False)
    _compile(fn, S((B, ell, N)), S((K, ell)), S((K, ell)), S((K, 1)),
             S((K, 1)), S((K, 1)))


@pytest.mark.parametrize("G,R,L", [
    (1, 1, 60),                 # one rotation at the top level (Q_48 ∪ P)
    (1, 2, 60),                 # a hoisted set of two rotations
    (4, 4, 58),                 # four distinct ciphertexts, one wave
])
def test_auto_ks_compiles_for_v5e(one_chip, no_persistent_cache, G, R, L):
    from repro.kernels.automorphism.kernel import auto_ks_pallas
    J = 4
    S = lambda shape, dt=jnp.uint32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    fn = lambda *a: auto_ks_pallas(*a, interpret=False)
    _compile(fn, S((J, G, L, N)), S((R, J, L, N)), S((R, J, L, N)),
             S((R, N), jnp.int32), *[S((L, 1))] * 5)


@pytest.mark.parametrize("name", ["ntt", "intt"])
def test_ring_ntt_compiles_lane_dense_for_v5e(one_chip, no_persistent_cache,
                                              name):
    """The production NTT programs at a batch of two 48-limb polynomials:
    still named ``jit_ntt``/``jit_intt`` (the device trace finds them by
    name), and lane-dense — at most 2,800 MB of HBM traffic by the
    compiler's own count, where the iterative transform with its butterflies
    on the lane axis counts 5,396 (forward) and 5,090 MB (inverse)."""
    from repro.core import ntt as nttm, poly
    ell = 48
    R, C = nttm.lane_split(N)
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.uint32,
                                            sharding=one_chip)
    shapes = {"q": (ell, 1), "r_inv": (ell, 1), "r_inv_shoup": (ell, 1),
              "c_inv": (ell, 1), "c_inv_shoup": (ell, 1)}
    lc = nttm.LaneConsts(*[
        S(*shapes.get(f, (ell, R, C) if f.startswith("twiddle") else
                      (ell, C - 1) if f.startswith("row_") else (ell, R)))
        for f in nttm.LaneConsts._fields])
    fn = poly._ntt if name == "ntt" else poly._intt
    compiled = fn.lower(S(2, ell, N), lc).compile()
    assert compiled.as_text().startswith(f"HloModule jit_{name}")
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] <= 2.8e9
