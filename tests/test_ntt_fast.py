"""Fast-path NTT coverage (EXPERIMENTS.md §Perf): the gather-free/lazy
transforms must match the naive big-int oracle AND the pre-overhaul eager
path bit-for-bit, lazy-reduction intermediates must stay below 2q, and the
batched Pallas grid must be invariant in ``limbs_per_block``."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import const_cache, modmath as mm, ntt as nttm, rns
from repro.kernels.ntt import ops as ntt_ops, ref as ntt_ref


def rand_limbs(basis, N, seed=0):
    rng = np.random.default_rng(seed)
    return np.stack([rng.integers(0, q, N, dtype=np.int64).astype(np.uint32)
                     for q in basis])


# ------------------------------------------------------- lazy modmath bounds

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_lazy_ops_stay_below_2q(seed):
    """addmod/submod_lazy: [0,2q)² → [0,2q); shoup_lazy: any u32 → [0,2q)."""
    rng = np.random.default_rng(seed)
    q = int(rns.gen_ntt_primes(1, 1 << 10)[0])
    qv = jnp.uint32(q)
    two_q = jnp.uint32(2 * q)
    a = jnp.asarray(rng.integers(0, 2 * q, 4096, dtype=np.int64).astype(np.uint32))
    b = jnp.asarray(rng.integers(0, 2 * q, 4096, dtype=np.int64).astype(np.uint32))
    s = np.asarray(mm.addmod_lazy(a, b, two_q)).astype(np.uint64)
    d = np.asarray(mm.submod_lazy(a, b, two_q)).astype(np.uint64)
    assert (s < 2 * q).all() and (d < 2 * q).all()
    # exactness vs python ints
    an, bn = np.asarray(a, dtype=np.uint64), np.asarray(b, dtype=np.uint64)
    np.testing.assert_array_equal(s % q, (an + bn) % q)
    np.testing.assert_array_equal(d % q, (an + 4 * q - bn) % q)
    # shoup_lazy accepts the FULL u32 range, not just [0, 2q)
    x = jnp.asarray(rng.integers(0, 1 << 32, 4096, dtype=np.int64).astype(np.uint32))
    w = int(rng.integers(1, q))
    ws = rns.shoup(w, q)
    r = np.asarray(mm.mulmod_shoup_lazy(x, jnp.uint32(w), jnp.uint32(ws), qv))
    assert (r.astype(np.uint64) < 2 * q).all()
    np.testing.assert_array_equal(r.astype(np.uint64) % q,
                                  np.asarray(x, dtype=np.uint64) * w % q)
    full = np.asarray(mm.mulmod_shoup(x, jnp.uint32(w), jnp.uint32(ws), qv))
    np.testing.assert_array_equal(full, r.astype(np.uint64) % q)


@pytest.mark.parametrize("N", [64, 256, 1024])
def test_ntt_lazy_intermediates_below_2q(N):
    """The lazy forward keeps every output strictly below 2q (the invariant
    the final reduce_once pass relies on)."""
    basis = tuple(rns.gen_ntt_primes(3, N))
    c = nttm.stacked_ntt_consts(basis, N)
    x = rand_limbs(basis, N, seed=N)
    lazy = np.asarray(nttm._ntt_lazy(jnp.asarray(x), c)).astype(np.uint64)
    qs = np.array(basis, dtype=np.uint64).reshape(-1, 1)
    assert (lazy < 2 * qs).all()
    np.testing.assert_array_equal(
        np.asarray(mm.reduce_once(jnp.asarray(lazy.astype(np.uint32)),
                                  jnp.asarray(c.q))),
        np.asarray(nttm.ntt(jnp.asarray(x), c)))


# -------------------------------------------- fast path vs eager path vs oracle

@pytest.mark.parametrize("N", [16, 64, 256])
def test_fast_matches_eager_and_naive(N):
    basis = tuple(rns.gen_ntt_primes(2, N))
    c = nttm.stacked_ntt_consts(basis, N)
    x = rand_limbs(basis, N, seed=N + 7)
    fast = np.asarray(nttm.ntt(jnp.asarray(x), c))
    eager = np.asarray(nttm.ntt_eager(jnp.asarray(x), c))
    np.testing.assert_array_equal(fast, eager)
    for i, q in enumerate(basis):
        np.testing.assert_array_equal(fast[i], nttm.naive_ntt(x[i], q, N))
    # inverse: both paths invert the fast forward exactly
    np.testing.assert_array_equal(
        np.asarray(nttm.intt(jnp.asarray(fast), c)), x)
    np.testing.assert_array_equal(
        np.asarray(nttm.intt_eager(jnp.asarray(fast), c)), x)


@pytest.mark.parametrize("N", [64, 256, 1024])
def test_four_step_fast_matches_eager_every_split(N):
    basis = tuple(rns.gen_ntt_primes(2, N))
    c = nttm.stacked_ntt_consts(basis, N)
    x = rand_limbs(basis, N, seed=N + 11)
    want = np.asarray(nttm.ntt(jnp.asarray(x), c))
    R = 2
    while R <= N // 2:
        fc = nttm.stacked_four_step_consts(basis, N, R)
        fast = np.asarray(nttm.four_step_ntt(jnp.asarray(x), fc))
        eager = np.asarray(nttm.four_step_ntt_eager(jnp.asarray(x), fc))
        np.testing.assert_array_equal(fast, want, err_msg=f"R={R}")
        np.testing.assert_array_equal(eager, want, err_msg=f"eager R={R}")
        back = np.asarray(nttm.four_step_intt(jnp.asarray(fast), fc))
        back_e = np.asarray(nttm.four_step_intt_eager(jnp.asarray(fast), fc))
        np.testing.assert_array_equal(back, x, err_msg=f"inv R={R}")
        np.testing.assert_array_equal(back_e, x, err_msg=f"inv eager R={R}")
        R *= 2


def test_bitrev_permute_is_the_gather():
    for N in (2, 8, 64, 1024):
        x = np.arange(3 * N, dtype=np.uint32).reshape(3, N)
        brev = rns.bitrev_indices(N)
        np.testing.assert_array_equal(np.asarray(nttm.bitrev_permute(x)),
                                      x[:, brev])
        # self-inverse
        np.testing.assert_array_equal(
            np.asarray(nttm.bitrev_permute(nttm.bitrev_permute(x))), x)


# ------------------------------------------------------- batched Pallas grid

def test_kernel_limbs_per_block_invariance():
    N, ell = 128, 6
    basis = tuple(rns.gen_ntt_primes(ell, N))
    rng = np.random.default_rng(5)
    x = np.stack([rand_limbs(basis, N, seed=s) for s in (1, 2)])
    want = ntt_ref.ntt_ref(x, basis)
    for lpb in (1, 2, 3, 4, 5, 6, None):
        got = np.asarray(ntt_ops.ntt_fwd(jnp.asarray(x), basis,
                                         limbs_per_block=lpb))
        np.testing.assert_array_equal(got, want, err_msg=f"lpb={lpb}")
        back = np.asarray(ntt_ops.ntt_inv(jnp.asarray(got), basis,
                                          limbs_per_block=lpb))
        np.testing.assert_array_equal(back, x, err_msg=f"inv lpb={lpb}")


def test_effective_limbs_per_block_divisor_fallback():
    from repro.kernels.ntt.kernel import effective_limbs_per_block
    assert effective_limbs_per_block(6, 4) == 3      # 4 ∤ 6 → largest ≤ 4
    assert effective_limbs_per_block(6, 6) == 6
    assert effective_limbs_per_block(7, 4) == 1      # prime ℓ
    assert effective_limbs_per_block(8, None) == 4   # default block of 4
    assert effective_limbs_per_block(2, 16) == 2     # clamped to ℓ


@pytest.mark.parametrize("R", [4, 32])
def test_kernel_R_sweep_lazy_vs_oracle(R):
    N = 512
    basis = tuple(rns.gen_ntt_primes(2, N))
    x = np.stack([rand_limbs(basis, N, seed=R + 1)])
    want = ntt_ref.ntt_ref(x, basis)
    got = np.asarray(ntt_ops.ntt_fwd(jnp.asarray(x), basis, R=R))
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------------ device constant cache

def test_device_const_cache_staged_once():
    N = 64
    basis = tuple(rns.gen_ntt_primes(2, N))
    c1 = const_cache.device_ntt_consts(basis, N)
    c2 = const_cache.device_ntt_consts(basis, N)
    assert c1 is c2
    assert isinstance(c1.psi_rev, jnp.ndarray)
    fc1 = const_cache.device_four_step_consts(basis, N, 8)
    fc2 = const_cache.device_four_step_consts(basis, N, 8)
    assert fc1 is fc2
    assert isinstance(fc1.row_stage, jnp.ndarray)
    # the device copies compute exactly what the numpy-backed consts compute
    x = rand_limbs(basis, N, seed=3)
    np.testing.assert_array_equal(
        np.asarray(nttm.ntt(jnp.asarray(x), c1)),
        np.asarray(nttm.ntt(jnp.asarray(x), nttm.stacked_ntt_consts(basis, N))))


def test_stage_major_row_tables_cover_all_stages():
    """row_stage[m-1:2m-1] must equal the strided subsampling of row_pow."""
    N, R = 256, 8
    basis = tuple(rns.gen_ntt_primes(1, N))
    fc = nttm.stacked_four_step_consts(basis, N, R)
    C = fc.C
    m = 1
    while m < C:
        stride = C // (2 * m)
        np.testing.assert_array_equal(fc.row_stage[:, m - 1:2 * m - 1],
                                      fc.row_pow[:, ::stride][:, :m])
        np.testing.assert_array_equal(fc.row_stage_inv[:, m - 1:2 * m - 1],
                                      fc.row_pow_inv[:, ::stride][:, :m])
        m *= 2


# ------------------------------------------- lane-dense production transform

_NTT, _INTT = jax.jit(nttm.ntt), jax.jit(nttm.intt)


def _lane_fns():
    """Fresh jits of the lane-dense pair, so a patched stage grouping is
    traced anew."""
    return (jax.jit(lambda x, lc: nttm.ntt_lanes(x, lc)),
            jax.jit(lambda x, lc: nttm.intt_lanes(x, lc)))


def _lazy(y, basis, seed):
    """y plus q on a random half of the entries: the lazy range below 2q."""
    rng = np.random.default_rng(seed)
    qs = np.array(basis, dtype=np.uint32).reshape(-1, 1)
    return y + qs * rng.integers(0, 2, y.shape).astype(np.uint32)


@pytest.mark.parametrize("N,split", [
    (1 << 7, (8, 16)), (1 << 8, (2, 128)), (1 << 10, (8, 128)),
    (1 << 12, (32, 128)), (1 << 15, (128, 256)), (1 << 16, (256, 256)),
    (1 << 2, (2, 2)), (1 << 3, (2, 4)), (1 << 6, (8, 8))])
def test_lane_split_rule(N, split):
    """R ≥ 2 rows, C the power of two nearest √N from above, raised to 128
    lanes where N ≥ 2⁸ allows it."""
    assert nttm.lane_split(N) == split


def test_lane_split_needs_two_rows_and_columns():
    with pytest.raises(ValueError):
        nttm.lane_split(2)


@pytest.mark.parametrize("N", [1 << 8, 1 << 10, 1 << 12, 1 << 6, 1 << 7])
@pytest.mark.parametrize("lead", [(), (2,), (2, 3)])
def test_lanes_match_iterative_and_naive(N, lead):
    """The rule's split: bit-equal to ``ntt``/``intt`` (lazy inverse inputs
    included) and to ``naive_ntt`` (one limb row, N ≤ 2¹⁰), and a round
    trip."""
    basis = tuple(rns.gen_ntt_primes(2, N))
    c = nttm.stacked_ntt_consts(basis, N)
    R, _ = nttm.lane_split(N)
    lc = nttm.lane_consts(nttm.stacked_four_step_consts(basis, N, R))
    rng = np.random.default_rng(N + len(lead))
    x = np.stack([rng.integers(0, q, (*lead, N)) for q in basis],
                 axis=-2).astype(np.uint32)
    fwd, inv = _lane_fns()
    got = np.asarray(fwd(x, lc))
    np.testing.assert_array_equal(got, np.asarray(_NTT(x, c)))
    if N <= 1 << 10 and not lead:
        np.testing.assert_array_equal(got[1], nttm.naive_ntt(x[1], basis[1], N))
    lazy = _lazy(got, basis, N)
    back = np.asarray(inv(lazy, lc))
    np.testing.assert_array_equal(back, np.asarray(_INTT(lazy, c)))
    np.testing.assert_array_equal(back, x)


@pytest.mark.parametrize("group", [1, 2, 3, 4])
@pytest.mark.parametrize("N", [1 << 10, 1 << 7])
def test_lanes_every_split_and_stage_grouping(monkeypatch, N, group):
    """Every split the rule picks among (C ≥ 128 where N ≥ 2⁸ allows it,
    else any R, C ≥ 2) and every stage grouping, including last groups
    shorter than the rest."""
    monkeypatch.setattr(nttm, "STAGE_GROUP", group)
    basis = tuple(rns.gen_ntt_primes(2, N))
    c = nttm.stacked_ntt_consts(basis, N)
    x = rand_limbs(basis, N, seed=N + group)
    want = np.asarray(_NTT(x, c))
    lazy = _lazy(want, basis, group)
    want_inv = np.asarray(_INTT(lazy, c))
    R = 2
    while N // R >= (nttm.LANES if N >= 2 * nttm.LANES else 2):
        lc = nttm.lane_consts(nttm.stacked_four_step_consts(basis, N, R))
        fwd, inv = _lane_fns()
        np.testing.assert_array_equal(np.asarray(fwd(x, lc)), want,
                                      err_msg=f"R={R}")
        np.testing.assert_array_equal(np.asarray(inv(lazy, lc)), want_inv,
                                      err_msg=f"inv R={R}")
        R *= 2


def test_lanes_at_the_paper_degree():
    """N = 2¹⁶, 2 limbs, the 256×256 split the rule picks: bit-equal to the
    iterative pair, lazy inverse inputs included, and a round trip."""
    N = 1 << 16
    basis = tuple(rns.gen_ntt_primes(2, N))
    c = const_cache.device_ntt_consts(basis, N)
    lc = const_cache.device_lane_consts(basis, N)
    assert lc.twiddle.shape == (2, 256, 256)
    x = rand_limbs(basis, N, seed=16)
    fwd, inv = _lane_fns()
    got = np.asarray(fwd(x, lc))
    np.testing.assert_array_equal(got, np.asarray(_NTT(x, c)))
    lazy = _lazy(got, basis, 16)
    back = np.asarray(inv(lazy, lc))
    np.testing.assert_array_equal(back, np.asarray(_INTT(lazy, c)))
    np.testing.assert_array_equal(back, x)


def test_ring_ops_take_the_lane_path_by_shape():
    """``RnsPoly.to_ntt``/``to_coeff`` run the lane-dense transform at every
    N, below 2⁸ too, with the iterative pair's results."""
    from repro.core import poly
    assert poly._ntt.__name__ == "ntt" and poly._intt.__name__ == "intt"
    for N in (1 << 7, 1 << 10):
        basis = tuple(rns.gen_ntt_primes(3, N))
        lc = poly.transform_consts(basis, N)
        assert isinstance(lc, nttm.LaneConsts)
        assert lc.twiddle.shape[1:] == nttm.lane_split(N)
        x = rand_limbs(basis, N, seed=N)
        p = poly.RnsPoly(jnp.asarray(x), basis, poly.COEFF).to_ntt()
        c = nttm.stacked_ntt_consts(basis, N)
        np.testing.assert_array_equal(np.asarray(p.data),
                                      np.asarray(_NTT(x, c)))
        np.testing.assert_array_equal(np.asarray(p.to_coeff().data), x)


def test_ring_ops_stage_only_lane_tables_and_limb_scalars():
    """A basis the ring ops meet stages the lane tables and (ℓ, 1) scalar
    columns, never the iterative transform's (ℓ, N) ψ tables."""
    from repro.core import poly
    N = 1 << 9
    basis = tuple(rns.gen_ntt_primes(5, N))[2:]
    misses = const_cache.device_ntt_consts.cache_info().misses
    x = poly.RnsPoly(jnp.asarray(rand_limbs(basis, N, seed=9)), basis,
                     poly.COEFF).to_ntt()
    y = ((x * x + x - x).mul_scalar(np.arange(1, 4)) + (-x)).to_coeff()
    jax.block_until_ready(y.data)
    assert const_cache.device_ntt_consts.cache_info().misses == misses
    lc = x.c()
    assert isinstance(lc, const_cache.LimbConsts)
    assert all(f.shape == (3, 1) for f in lc)
