"""Two-sample Kolmogorov-Smirnov machinery for the LEM similarity measure.

The paper (Sec. III-A) uses the two-sample KS test as the exchangeability
measure: statistic D = sup_x |F1(x) - F2(x)| (eq. 1), standardized by
sqrt(n1*n2/(n1+n2)) (eq. 2), mapped to a p-value with the asymptotic
Kolmogorov distribution.  A block is exchangeable with a stored source
distribution when p >= alpha.

TPU adaptation (DESIGN.md Sec. 2): the p-value is monotone in the statistic,
so the alpha threshold is converted ONCE (host-side) into a critical distance
``critical_distance(alpha, n1, n2)`` and the hot loop compares plain distances.
The p-value path is kept for analysis benchmarks (Fig. 3).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

__all__ = [
    "kolmogorov_sf",
    "ks_pvalue",
    "ks_statistic",
    "ks_statistic_sorted",
    "ks_statistic_many",
    "ks_statistic_many_masked",
    "critical_distance",
]

_SERIES_TERMS = 40

# Below this the alternating series needs more terms than we carry: the
# partial sums of the even-truncated series cancel as lam -> 0 (Q(0) came
# out 0.0 instead of 1.0).  The true survival function satisfies
# 1 - Q(0.1) ~ 4e-53, far below f64 resolution, so returning exactly 1.0
# under the cutoff agrees with scipy.special.kolmogorov to machine
# precision while the series itself is accurate (truncation < 3e-15) above.
_SMALL_LAM = 0.1


def kolmogorov_sf(lam):
    """Survival function of the Kolmogorov distribution.

    Q_KS(lam) = 2 * sum_{j>=1} (-1)^{j-1} exp(-2 j^2 lam^2), clipped to [0,1].
    For lam < 0.1 the truncated series is replaced by its limit 1.0 (the
    scipy small-lam regime, where 1 - Q(lam) underflows f64); above the
    cutoff it matches scipy.special.kolmogorov to ~1e-8 in f32, ~1e-14 in
    f64.
    """
    lam = jnp.asarray(lam)
    j = jnp.arange(1, _SERIES_TERMS + 1, dtype=lam.dtype if jnp.issubdtype(lam.dtype, jnp.floating) else jnp.float32)
    lam_ = jnp.maximum(lam, 1e-12)
    terms = jnp.where(
        (j % 2) == 1, 1.0, -1.0
    ) * jnp.exp(-2.0 * (j ** 2)[..., :] * (lam_[..., None] ** 2))
    q = 2.0 * jnp.sum(terms, axis=-1)
    return jnp.where(lam_ < _SMALL_LAM, 1.0, jnp.clip(q, 0.0, 1.0))


def ks_pvalue(d, n1, n2):
    """Asymptotic two-sided two-sample KS p-value (scipy ``mode='asymp'``).

    Includes the small-lam special case: for sqrt(n1*n2/(n1+n2))*d < 0.1
    (in particular d == 0, identical samples) the p-value is exactly 1.0,
    not the cancelled partial sum the raw series produces.
    """
    d = jnp.asarray(d)
    en = (n1 * n2) / (n1 + n2)
    return kolmogorov_sf(jnp.sqrt(en) * d)


def _ecdf_distance_sorted(xs, ys):
    """sup_x |F_x - F_y| for sorted 1-D samples xs (n1,), ys (n2,).

    Evaluated at every sample point of both samples (ECDFs are right-
    continuous step functions, the sup is attained at a jump point).  A
    sample's own ECDF at its points is a ``side="right"`` count too, so
    tied values (a constant block) take the count at the end of their run;
    without ties it is ``1..n``.
    """
    n1 = xs.shape[0]
    n2 = ys.shape[0]
    # F at candidate points
    fx_at_x = jnp.searchsorted(xs, xs, side="right").astype(jnp.float32) / n1
    fy_at_x = jnp.searchsorted(ys, xs, side="right").astype(jnp.float32) / n2
    d1 = jnp.max(jnp.abs(fx_at_x - fy_at_x))
    # F at dictionary points
    fy_at_y = jnp.searchsorted(ys, ys, side="right").astype(jnp.float32) / n2
    fx_at_y = jnp.searchsorted(xs, ys, side="right").astype(jnp.float32) / n1
    d2 = jnp.max(jnp.abs(fx_at_y - fy_at_y))
    return jnp.maximum(d1, d2)


def ks_statistic_sorted(xs, ys):
    """KS statistic between two already-sorted samples."""
    return _ecdf_distance_sorted(jnp.asarray(xs), jnp.asarray(ys))


def ks_statistic(x, y):
    """KS statistic between two unsorted samples."""
    return _ecdf_distance_sorted(jnp.sort(jnp.asarray(x)), jnp.sort(jnp.asarray(y)))


def ks_statistic_many(xs_sorted, dict_sorted):
    """KS statistic of one sorted candidate vs a stack of sorted blocks.

    xs_sorted: (n,); dict_sorted: (D, n).  Returns (D,) float32.
    This is the pure-jnp oracle for the Pallas ``dict_match`` kernel.
    """
    return jax.vmap(lambda ys: _ecdf_distance_sorted(xs_sorted, ys))(dict_sorted)


def _ecdf_distance_sorted_masked(xs, ys, nf, col_ok):
    """``_ecdf_distance_sorted`` for width-padded sorted samples.

    Both samples share the logical length ``nf`` (float32 scalar, traced)
    and are padded on the tail with ``+inf`` to a common physical width;
    ``col_ok`` masks the real columns.  Because ``+inf`` pads sort last and
    never compare ``<=`` a finite sample, every ``searchsorted`` count at a
    real column equals its unpadded value, and the masked positions are
    zero-filled before the max (KS >= 0), so the result is bitwise
    identical to ``_ecdf_distance_sorted`` on the unpadded samples.
    """
    fx_at_x = jnp.searchsorted(xs, xs, side="right").astype(jnp.float32) / nf
    fy_at_x = jnp.searchsorted(ys, xs, side="right").astype(jnp.float32) / nf
    d1 = jnp.max(jnp.where(col_ok, jnp.abs(fx_at_x - fy_at_x), 0.0))
    fy_at_y = jnp.searchsorted(ys, ys, side="right").astype(jnp.float32) / nf
    fx_at_y = jnp.searchsorted(xs, ys, side="right").astype(jnp.float32) / nf
    d2 = jnp.max(jnp.where(col_ok, jnp.abs(fx_at_y - fy_at_y), 0.0))
    return jnp.maximum(d1, d2)


def ks_statistic_many_masked(xs_sorted, dict_sorted, nf, col_ok):
    """Masked ``ks_statistic_many`` for the mixed-mode (adaptive) encoder:
    candidate and dictionary rows are padded to a common width with +inf,
    ``nf``/``col_ok`` give the channel's logical sample count and real
    columns.  Bitwise identical to ``ks_statistic_many`` on the unpadded
    width (DESIGN.md Sec. 13)."""
    return jax.vmap(
        lambda ys: _ecdf_distance_sorted_masked(xs_sorted, ys, nf, col_ok)
    )(dict_sorted)


def critical_distance(alpha: float, n1: int, n2: int) -> float:
    """Invert the asymptotic p-value: largest D with p(D) >= alpha.

    Host-side scalar (numpy bisection); decision `p >= alpha` is exactly
    `D <= critical_distance(alpha, n1, n2)` up to float tolerance since the
    same series is used in both directions.
    """
    if not (0.0 < alpha < 1.0):
        raise ValueError(f"alpha must be in (0,1), got {alpha}")
    en = (n1 * n2) / (n1 + n2)

    def q(lam: float) -> float:
        if lam < _SMALL_LAM:
            return 1.0
        j = np.arange(1, _SERIES_TERMS + 1)
        val = 2.0 * np.sum((-1.0) ** (j - 1) * np.exp(-2.0 * j * j * lam * lam))
        return float(np.clip(val, 0.0, 1.0))

    lo, hi = 1e-9, 10.0
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if q(mid) >= alpha:
            lo = mid
        else:
            hi = mid
    return lo / np.sqrt(en)
