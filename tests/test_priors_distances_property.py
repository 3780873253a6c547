"""Property tests for core/priors.py and core/distances.py.

Hypothesis-driven where available (nightly CI installs it); each property
also has a seeded non-hypothesis variant so tier-1 keeps coverage in
environments without the package (see tests/_hypothesis_compat.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

from repro.core.distances import DISTANCES
from repro.core.priors import UniformBoxPrior

# float32 strategies need bounds a float32 holds exactly
F32_0_01 = float(np.float32(0.01))

# a deliberately lopsided box: zero-width-adjacent, negative lows, big highs
BOXES = [
    ((1.0, 100.0, 2.0), None),
    ((2.5, 0.1, 7.0, 1.0), (-1.0, 0.0, 3.0, 0.5)),
    ((1e-3,), (-1e-3,)),
]


# ---------------------------------------------------------------- priors
@pytest.mark.parametrize("highs,lows", BOXES)
def test_prior_samples_inside_box(highs, lows):
    prior = UniformBoxPrior(highs=highs, lows=lows)
    th = np.asarray(prior.sample(jax.random.PRNGKey(0), (4096,)))
    lo = np.asarray(prior.lows)
    hi = np.asarray(prior.highs)
    assert (th >= lo).all() and (th <= hi).all()
    # every dimension actually spreads over its box (not collapsed)
    span = th.max(axis=0) - th.min(axis=0)
    assert (span > 0.5 * (hi - lo)).all()


@pytest.mark.parametrize("highs,lows", BOXES)
def test_prior_log_pdf_finite_exactly_inside(highs, lows):
    prior = UniformBoxPrior(highs=highs, lows=lows)
    lo = np.asarray(prior.lows, np.float32)
    hi = np.asarray(prior.highs, np.float32)
    inside = (lo + hi) / 2.0
    on_edge = hi.copy()
    outside = hi + (hi - lo) * 0.01 + 1e-6
    lp = np.asarray(prior.log_pdf(jnp.asarray([inside, on_edge, outside])))
    assert np.isfinite(lp[0])
    assert np.isfinite(lp[1])  # closed box: the boundary is inside
    assert lp[2] == -np.inf
    # the density integrates to one => log_pdf == -log(volume)
    np.testing.assert_allclose(lp[0], -np.sum(np.log(hi - lo)), rtol=1e-5)


@given(
    lows=st.lists(st.floats(-10, 10, allow_nan=False, width=32), min_size=1,
                  max_size=6),
    widths=st.lists(st.floats(F32_0_01, 20, allow_nan=False, width=32), min_size=1,
                    max_size=6),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=30, deadline=None)
def test_prior_sample_logpdf_consistent(lows, widths, seed):
    n = min(len(lows), len(widths))
    lows = tuple(lows[:n])
    highs = tuple(l + w for l, w in zip(lows, widths[:n]))
    prior = UniformBoxPrior(highs=highs, lows=lows)
    th = prior.sample(jax.random.PRNGKey(seed), (256,))
    lp = np.asarray(prior.log_pdf(th))
    assert np.isfinite(lp).all()  # own samples always have finite log-prob
    th_np = np.asarray(th)
    assert (th_np >= np.asarray(lows, np.float32)).all()
    assert (th_np <= np.asarray(highs, np.float32)).all()


# -------------------------------------------------------------- distances
def _fake_series(key, batch=32, channels=3, days=20):
    ks, ko = jax.random.split(jax.random.PRNGKey(key))
    sim = jax.random.uniform(ks, (batch, channels, days), jnp.float32) * 1e3
    obs = jax.random.uniform(ko, (channels, days), jnp.float32) * 1e3
    return sim, obs


@pytest.mark.parametrize("name", sorted(DISTANCES))
def test_distance_nonnegative_and_zero_on_identical(name):
    dist = DISTANCES[name]
    sim, obs = _fake_series(0)
    d = np.asarray(dist(sim, obs))
    assert d.shape == (sim.shape[0],)
    assert (d >= 0).all()
    # a batch row equal to the observation has distance exactly zero
    sim_eq = sim.at[3].set(obs)
    d_eq = np.asarray(dist(sim_eq, obs))
    assert d_eq[3] == 0.0


@pytest.mark.parametrize("name", sorted(DISTANCES))
def test_distance_permutation_stable_across_batch(name):
    """Permuting the batch axis permutes distances identically — no row may
    influence another's distance (the independence ABC relies on)."""
    dist = DISTANCES[name]
    sim, obs = _fake_series(1)
    perm = np.random.default_rng(0).permutation(sim.shape[0])
    d = np.asarray(dist(sim, obs))
    d_perm = np.asarray(dist(sim[perm], obs))
    np.testing.assert_array_equal(d[perm], d_perm)


@given(
    seed=st.integers(0, 2**31 - 1),
    scale=st.floats(F32_0_01, 1e4, allow_nan=False, width=32),
)
@settings(max_examples=25, deadline=None)
def test_distance_triangle_like_properties(seed, scale):
    """Euclidean distance: symmetry under sim/obs swap and absolute
    homogeneity under scaling of the difference."""
    sim, obs = _fake_series(seed % 1000, batch=4)
    dist = DISTANCES["euclidean"]
    d = np.asarray(dist(sim, obs))
    # swap: d(sim_i, obs) == d(obs_broadcast, sim_i) computed rowwise
    d_swapped = np.asarray(
        jnp.stack([dist(obs[None], sim[i]) for i in range(4)]).ravel()
    )
    np.testing.assert_allclose(d, d_swapped, rtol=1e-5)
    # homogeneity: scaling both by c scales the distance by c
    d_scaled = np.asarray(dist(sim * scale, obs * scale))
    np.testing.assert_allclose(d_scaled, d * scale, rtol=1e-4)


def test_hypothesis_shim_status():
    """Document (in the test report) whether the property tests above ran
    under hypothesis or as seeded fallbacks."""
    assert HAVE_HYPOTHESIS in (True, False)
