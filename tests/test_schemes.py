"""Unit tests for the coding schemes: exponents, thresholds, exact recovery.

Reproduces the paper's core claims at test scale:
  * BEC threshold tau = mn (Sec. III-B), recovery from ANY tau workers
  * tradeoff threshold tau = mnp' + p' - 1 (Sec. IV) + Example 1 exponents
  * baseline polynomial code tau = pmn + p - 1 [Yu et al.]
  * digit extraction with sign recovery (Sec. III-C)
"""
import numpy as np
import pytest

import jax

jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp  # noqa: E402

from repro.core import (  # noqa: E402
    coded_matmul,
    digit_extract,
    make_plan,
    make_scheme,
    uncoded_matmul,
)


def _rand_pair(rng, v=48, r=32, t=24, lo=-5, hi=6):
    A = rng.integers(lo, hi, size=(v, r)).astype(np.float64)
    B = rng.integers(lo, hi, size=(v, t)).astype(np.float64)
    return A, B


class TestThresholds:
    @pytest.mark.parametrize("p,m,n", [(2, 2, 2), (3, 2, 2), (4, 3, 2), (2, 1, 1)])
    def test_bec_tau_optimal(self, p, m, n):
        assert make_scheme("bec", p, m, n).tau == m * n

    @pytest.mark.parametrize("p,m,n,pp", [(4, 2, 2, 2), (4, 2, 2, 4), (6, 2, 3, 3)])
    def test_tradeoff_tau(self, p, m, n, pp):
        assert make_scheme("tradeoff", p, m, n, p_prime=pp).tau == m * n * pp + pp - 1

    @pytest.mark.parametrize("p,m,n", [(2, 2, 2), (3, 2, 2)])
    def test_polycode_tau(self, p, m, n):
        assert make_scheme("polycode", p, m, n).tau == p * m * n + p - 1

    def test_tradeoff_pprime1_is_bec_tau(self):
        assert make_scheme("tradeoff", 4, 2, 3, p_prime=1).tau == \
            make_scheme("bec", 4, 2, 3).tau

    def test_tradeoff_invalid_pprime(self):
        with pytest.raises(ValueError):
            make_scheme("tradeoff", 4, 2, 2, p_prime=3)


class TestExample1:
    """Paper Sec. IV Example 1: m=n=2, p=4, p'=2."""

    def test_useful_powers(self):
        sch = make_scheme("tradeoff", 4, 2, 2, p_prime=2)
        assert sorted(sch.useful_z_exp().ravel().tolist()) == [1, 3, 5, 7]

    def test_degree(self):
        sch = make_scheme("tradeoff", 4, 2, 2, p_prime=2)
        az, _ = sch.a_exponents()
        bz, _ = sch.b_exponents()
        assert az.max() + bz.max() == sch.tau - 1 == 8

    def test_digit_depth(self):
        sch = make_scheme("tradeoff", 4, 2, 2, p_prime=2)
        assert sch.digit_depth == 1  # |X| <= 2L^2 vs BEC's 8L^4


class TestExactRecovery:
    @pytest.mark.parametrize("kind,p,pp", [("bec", 2, 1), ("bec", 3, 1),
                                           ("polycode", 2, 1),
                                           ("tradeoff", 4, 2)])
    def test_no_erasure(self, rng, kind, p, pp):
        A, B = _rand_pair(rng)
        L = 48 * 5 * 5 + 1
        plan = make_plan(kind, p, 2, 2, K=plan_k(kind, p, 2, 2, pp),
                         L=L, points="chebyshev", p_prime=pp)
        C = coded_matmul(A, B, plan)
        np.testing.assert_array_equal(np.asarray(C), np.asarray(uncoded_matmul(A, B)))

    @pytest.mark.parametrize("survivor_seed", range(4))
    def test_any_tau_subset(self, rng, survivor_seed):
        """ANY tau of K workers decode exactly (unit-circle: conditioning-free)."""
        A, B = _rand_pair(rng)
        L = 48 * 5 * 5 + 1
        plan = make_plan("bec", 2, 2, 2, K=10, L=L, points="unit_circle")
        srng = np.random.default_rng(survivor_seed)
        surv = srng.choice(10, size=plan.tau, replace=False).tolist()
        C = coded_matmul(A, B, plan, survivors=surv)
        np.testing.assert_allclose(np.asarray(C),
                                   np.asarray(uncoded_matmul(A, B)), atol=1e-9)

    def test_max_erasures(self, rng):
        """K - tau = 6 erasures with the paper's Sec. V geometry."""
        A, B = _rand_pair(rng)
        L = 48 * 5 * 5 + 1
        plan = make_plan("bec", 2, 2, 2, K=10, L=L, points="unit_circle")
        C = coded_matmul(A, B, plan, erased=[0, 2, 4, 6, 8, 9])
        np.testing.assert_allclose(np.asarray(C),
                                   np.asarray(uncoded_matmul(A, B)), atol=1e-9)

    def test_below_threshold_rejected(self, rng):
        A, B = _rand_pair(rng)
        plan = make_plan("bec", 2, 2, 2, K=6, L=100, points="chebyshev")
        with pytest.raises(ValueError, match="undecodable"):
            coded_matmul(A, B, plan, erased=[0, 1, 2])

    def test_negative_entries_sign_recovery(self, rng):
        A, B = _rand_pair(rng, lo=-9, hi=10)
        L = 48 * 9 * 9 + 1
        plan = make_plan("bec", 2, 2, 2, K=6, L=L, points="chebyshev")
        C = coded_matmul(A, B, plan, erased=[3])
        np.testing.assert_array_equal(np.asarray(C), np.asarray(uncoded_matmul(A, B)))

    def test_nonsquare_padding(self, rng):
        """Dims not divisible by the grid: zero-padding stays exact."""
        A = rng.integers(-3, 4, size=(50, 33)).astype(np.float64)
        B = rng.integers(-3, 4, size=(50, 17)).astype(np.float64)
        plan = make_plan("bec", 2, 2, 2, K=6, L=50 * 3 * 3 + 1, points="chebyshev")
        C = coded_matmul(A, B, plan)
        np.testing.assert_array_equal(np.asarray(C), np.asarray(uncoded_matmul(A, B)))


def plan_k(kind, p, m, n, pp):
    sch = make_scheme(kind, p, m, n, p_prime=pp)
    return sch.tau + 2


class TestDigitExtraction:
    def test_roundtrip(self, rng):
        s = 1 << 12
        C = rng.integers(-s // 2 + 1, s // 2, size=(64,)).astype(np.float64)
        hi = rng.integers(-100, 100, size=(64,)).astype(np.float64)
        lo = rng.uniform(-0.4, 0.4, size=64)
        X = jnp.asarray(C + hi * s + lo)
        out = digit_extract(X, float(s))
        np.testing.assert_array_equal(np.asarray(out), C)

    def test_power_of_two_exact(self):
        # s power of two: fp mod is exact even at large magnitudes
        s = float(1 << 30)
        X = jnp.asarray([(1 << 29) - 1 + (1 << 30) * 7.0])
        assert float(digit_extract(X, s)[0]) == (1 << 29) - 1

    @pytest.mark.parametrize("s", [1 << 26, 3 * (1 << 20) + 1, 40000002],
                             ids=["pow2", "odd", "even"])
    def test_matches_integer_residue_at_large_magnitude(self, rng, s):
        # near multiples of s with |R| up to 2^52, plus the +-s/2 edges
        k = rng.integers(-(1 << 52) // s, (1 << 52) // s, size=512)
        edges = np.array([0, 1, -1, s // 2, s // 2 + 1, -(s // 2),
                          -(s // 2) - 1, s - 1])
        R = np.concatenate([k * s + rng.integers(-3, 4, size=512),
                            k[:edges.size] * s + edges])
        want = [c if c <= s / 2 else c - s for c in (int(x) % s for x in R)]
        out = digit_extract(jnp.asarray(R.astype(np.float64)), float(s))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want, float))
