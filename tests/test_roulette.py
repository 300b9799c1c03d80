"""The RWA roulette's selection math (``kernels/common.py``): the log-depth
prefix scan and the first-crossing picks that the fused kernel, its jnp
oracle and the spin-sharded driver share."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy import stats

from repro.kernels import common

TINY = np.float32(2.0 ** -24)


@pytest.mark.parametrize("k", [1, 2, 3, 16, 56, 125, 128, 160])
def test_prefix_sum_matches_float64_cumsum(k):
    """Within K ulps of the exact prefix, and the same bits eagerly and
    under ``jit`` (the order is fixed by the levels, not by a compiler)."""
    rng = np.random.default_rng(k)
    x = rng.random((8, k), dtype=np.float32)
    x[:, ::3] = 0.0                          # zero weights, as cold sites give
    got = np.asarray(common.prefix_sum(jnp.asarray(x)))
    exact = np.cumsum(x.astype(np.float64), axis=1)
    ulp = np.spacing(exact.astype(np.float32))
    assert np.all(np.abs(got - exact) <= k * ulp)
    jitted = np.asarray(jax.jit(common.prefix_sum)(jnp.asarray(x)))
    np.testing.assert_array_equal(got, jitted)


@pytest.mark.parametrize("n,lane", [(64, 8), (250, 125), (2000, 125)])
def test_roulette_pick_equals_searchsorted_on_exact_rows(n, lane):
    """Where every partial sum is exact in float32 (small integers, zeros
    included) the pick is the flat wheel's ``searchsorted(…, side="right")``,
    so a zero-weight site is never drawn."""
    rng = np.random.default_rng(n)
    r = 64
    p = rng.integers(0, 4, size=(r, n)).astype(np.float32)
    p[:, :lane] = 0.0                        # a whole block of zeros
    p[0, :] = 0.0
    p[0, n - 1] = 1.0                        # one live site, at the end
    u = (rng.integers(0, 2 ** 16, size=(r, 1)) / 2 ** 16).astype(np.float32)
    site, total, degenerate = common.roulette_pick(
        jnp.asarray(p), jnp.asarray(u), lane)
    site = np.asarray(site)[:, 0]
    cum = np.cumsum(p.astype(np.float64), axis=1)
    np.testing.assert_array_equal(np.asarray(total)[:, 0], cum[:, -1])
    assert not np.asarray(degenerate).any()
    radius = u[:, 0] * np.asarray(total)[:, 0]
    want = [np.searchsorted(cum[i], radius[i], side="right") for i in range(r)]
    np.testing.assert_array_equal(site, want)
    assert np.all(p[np.arange(r), site] > 0)


def test_first_crossing_on_non_monotone_prefix():
    """The doubling scan's prefixes need not rise: for [1, 2⁻²⁴, 2⁻²⁴, 0, 1,
    0, 0, 0] lane 2 sums to 1 + 2⁻²³ but lane 3 (a zero weight) rounds back
    to 1. At radius 1 the block pick takes lane 2, the first crossing —
    the ≤-count would take the zero-weight lane 3 — and the residual into
    it is ≥ 0."""
    blk = np.array([[1, TINY, TINY, 0, 1, 0, 0, 0]], np.float32)
    cb = np.asarray(common.prefix_sum(jnp.asarray(blk)))
    assert cb[0, 2] > cb[0, 3]               # the prefix falls by an ulp
    assert cb[0, 7] == 2.0
    g, residual, total, degenerate = common.roulette_block_pick(
        jnp.asarray(blk), jnp.full((1, 1), 0.5, jnp.float32))
    assert int(g[0, 0]) == 2
    assert int(np.sum(cb <= 1.0)) == 3      # what the count form would pick
    assert float(residual[0, 0]) >= 0.0
    assert float(total[0, 0]) == 2.0 and not bool(degenerate[0, 0])
    c = jnp.asarray(cb)
    assert int(common.first_crossing(c, jnp.full((1, 1), 1.0))[0, 0]) == 2
    assert int(common.first_crossing(c, jnp.full((1, 1), 0.5))[0, 0]) == 0
    # No lane exceeds the radius: clamped to the last lane.
    assert int(common.first_crossing(c, jnp.full((1, 1), 2.0))[0, 0]) == 7


@pytest.mark.parametrize("weights", [
    [0.0, 0.0, 0.0, 0.0],                    # W = 0
    [1.0, 1.0, 2.0 ** -23, 0.0],             # ulp-close prefixes
])
def test_roulette_lane_pick_first_crossing_crafted(weights):
    sel = jnp.asarray([weights], jnp.float32)
    cl = np.asarray(common.prefix_sum(sel))[0]
    for res in [0.0, 1.0, 2.0, float(cl[-1])]:
        got = int(common.roulette_lane_pick(sel, jnp.full((1, 1), res))[0, 0])
        above = np.flatnonzero(cl > np.float32(res))
        assert got == (above[0] if above.size else len(weights) - 1)
        if above.size:
            assert weights[got] > 0          # never a lane wholly below


def test_roulette_pick_frequencies_chi_square():
    """Over many uniforms on one fixed row, site j is drawn with
    probability p_j / W (zero-weight sites never)."""
    rng = np.random.default_rng(7)
    n, lane, draws = 64, 8, 50_000
    p = rng.random(n).astype(np.float32)
    p[[3, 17, 40]] = 0.0
    u = rng.random((draws, 1), dtype=np.float32)
    site, _, _ = jax.jit(common.roulette_pick, static_argnums=2)(
        jnp.broadcast_to(jnp.asarray(p), (draws, n)), jnp.asarray(u), lane)
    counts = np.bincount(np.asarray(site)[:, 0], minlength=n)
    live = p > 0
    assert counts[~live].sum() == 0
    expected = draws * p[live].astype(np.float64) / p.sum(dtype=np.float64)
    _, pvalue = stats.chisquare(counts[live], expected)
    assert pvalue > 1e-4


def test_roulette_pick_has_no_serial_loop():
    """The two prefix scans are a static unroll of log₂ K levels: the pick's
    jaxpr holds no ``while`` or ``scan``, so a K-deep serial loop cannot
    come back into every RWA step."""
    text = str(jax.make_jaxpr(lambda p, u: common.roulette_pick(p, u, 125))(
        jnp.zeros((8, 2000), jnp.float32), jnp.zeros((8, 1), jnp.float32)))
    assert " add " in text
    assert not re.search(r"\b(while|scan)\[", text)
    # The pin can see a loop: a masked fori_loop shows as a while.
    looped = str(jax.make_jaxpr(lambda x: jax.lax.fori_loop(
        0, x.shape[1], lambda k, a: a + x[:, :1], x))(jnp.zeros((8, 125))))
    assert re.search(r"\b(while|scan)\[", looped)
