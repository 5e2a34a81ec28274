"""KS statistic / p-value / critical distance vs scipy oracles."""
import numpy as np
import pytest
import scipy.special
import scipy.stats

from repro.core.ks import critical_distance, ks_pvalue, ks_statistic
from repro.core.npref import ks_pvalue_np, ks_statistic_np

try:  # only the property test needs hypothesis (optional dep)
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


@pytest.mark.parametrize("n1,n2", [(16, 16), (32, 32), (64, 31), (111, 111)])
def test_statistic_matches_scipy(n1, n2):
    rng = np.random.default_rng(0)
    for _ in range(5):
        x = rng.normal(size=n1)
        y = rng.normal(0.2, 1.3, size=n2)
        ref = scipy.stats.ks_2samp(x, y).statistic
        assert np.isclose(float(ks_statistic(x, y)), ref, atol=1e-7)
        assert np.isclose(ks_statistic_np(x, y), ref, atol=1e-12)


@pytest.mark.parametrize("case", ["constant", "quantized", "one_tie"])
def test_tied_samples_match_scipy_on_every_matcher(case):
    """Tied values (a constant block, quantized data, one repeated value)
    take the ECDF's count at the end of their run in every device
    matcher, as in scipy and the numpy oracle: a constant block against
    itself reads 0, not (n - 1) / n."""
    import jax.numpy as jnp

    from repro.core.ks import ks_statistic_many
    from repro.kernels.ops import dict_match
    from repro.kernels.ref import dict_match_ref

    rng = np.random.default_rng(3)
    n = 31
    if case == "constant":
        x, rows = np.zeros(n), [np.zeros(n), np.full(n, 1.0),
                                np.r_[np.zeros(20), np.ones(11)]]
    elif case == "quantized":
        x = np.round(rng.normal(size=n))
        rows = [np.round(rng.normal(size=n)) for _ in range(8)]
    else:
        x = rng.normal(size=n)
        x[5] = x[9]
        rows = [rng.normal(size=n) for _ in range(7)] + [x.copy()]
    xs = jnp.sort(jnp.asarray(x, jnp.float32))
    ds = jnp.sort(jnp.asarray(np.stack(rows), jnp.float32), axis=1)
    want = [scipy.stats.ks_2samp(x, r).statistic for r in rows]
    mn, mx = ds[:, 0], ds[:, -1]
    for got in (ks_statistic_many(xs, ds), dict_match(xs, ds, mn, mx, 0.5)[0],
                dict_match_ref(xs, ds, mn, mx, 0.5)[0]):
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)
    assert [ks_statistic_np(x, r) for r in rows] == pytest.approx(want)


def test_pvalue_matches_kolmogorov_sf():
    for n in [8, 16, 64, 256]:
        for d in [0.05, 0.1, 0.3, 0.7]:
            en = n * n / (2 * n)
            ref = scipy.special.kolmogorov(np.sqrt(en) * d)
            assert np.isclose(float(ks_pvalue(d, n, n)), ref, atol=1e-6)
            assert np.isclose(ks_pvalue_np(d, n, n), ref, atol=1e-9)


def test_critical_distance_inverts_pvalue():
    for alpha in [0.01, 0.05, 0.1, 0.2]:
        for n in [16, 32, 112, 255]:
            dc = critical_distance(alpha, n, n)
            # decision boundary: p(dc) == alpha
            assert np.isclose(ks_pvalue_np(dc, n, n), alpha, atol=1e-6)
            # monotone: slightly inside/outside flips the decision
            assert ks_pvalue_np(dc * 0.98, n, n) > alpha
            assert ks_pvalue_np(dc * 1.02, n, n) < alpha


def test_sensitivity_with_n():
    """Paper Fig. 3: same distance, larger n => smaller p-value."""
    ps = [ks_pvalue_np(0.2, n, n) for n in [8, 16, 32, 64, 128, 256]]
    assert all(a > b for a, b in zip(ps, ps[1:]))


@pytest.mark.parametrize("n", [4, 8, 16, 32, 64, 128, 255])
def test_identical_samples_always_accepted(n):
    """d=0 must give p == 1.0 exactly.  The asymptotic series used to
    collapse to 0 for small lambda (sum of zero terms); the small-lambda
    cutoff pins the fix in BOTH implementations, byte-consistently."""
    assert float(ks_pvalue(0.0, n, n)) == 1.0
    assert ks_pvalue_np(0.0, n, n) == 1.0
    # a tiny-but-nonzero distance still lands in the cutoff region
    assert ks_pvalue_np(1e-6, n, n) == 1.0
    # and an identical-block encode can therefore never KS-reject
    for alpha in [0.01, 0.05, 0.2]:
        assert ks_pvalue_np(0.0, n, n) > alpha


def test_small_lambda_agrees_with_scipy_asymp():
    """Across the cutoff: our p-values track scipy's asymptotic two-sample
    KS (mode="asymp") and never resurrect the small-lambda collapse."""
    rng = np.random.default_rng(7)
    for n in [16, 32, 64]:
        x = rng.normal(size=n)
        for d in [0.0, 1.0 / (4 * n), 1.0 / n, 2.0 / n, 0.2, 0.5]:
            p_ours = ks_pvalue_np(d, n, n)
            lam = np.sqrt(n / 2.0) * d  # en = n1*n2/(n1+n2) = n/2
            ref = scipy.special.kolmogorov(lam)
            if lam < 0.1:
                assert p_ours == 1.0  # cutoff region: exact by construction
            else:
                assert np.isclose(p_ours, ref, atol=1e-9)
        # end-to-end cross-check on a realized pair
        ref = scipy.stats.ks_2samp(x, x, method="asymp").pvalue
        assert ks_pvalue_np(ks_statistic_np(x, x), n, n) == pytest.approx(
            ref, abs=1e-12) == 1.0


if HAVE_HYPOTHESIS:
    @given(
        st.integers(min_value=4, max_value=128),
        st.integers(min_value=0, max_value=2**31 - 1),
    )
    @settings(max_examples=25, deadline=None)
    def test_statistic_properties(n, seed):
        rng = np.random.default_rng(seed)
        x, y = rng.normal(size=n), rng.normal(size=n)
        d = ks_statistic_np(x, y)
        assert 0.0 <= d <= 1.0
        assert ks_statistic_np(x, x) == 0.0
        # symmetry & permutation invariance
        assert np.isclose(d, ks_statistic_np(y, x), atol=1e-12)
        assert np.isclose(
            d, ks_statistic_np(rng.permutation(x), y), atol=1e-12)
