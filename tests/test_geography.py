"""Regional datasets with a geography: per-region populations and day-0
counts, and a mobility matrix carried by the dataset.

  * the engine's `xla` and `xla_fused` paths against a plain loop written
    from the model's equations, at R = 5 with unequal populations, seeds in
    several regions and a random row-stochastic mobility;
  * a geography that says what the defaults say (P/R each, one seeded
    region, the spec's matrix) gives the same distances bit for bit;
  * the bundled `usa_states` series and its gravity matrix;
  * `run_abc` through the device wave loop with the dataset's mobility, the
    mobility precedence, the Pallas refusal and the `region_days` counter.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import abc
from repro.core.abc import ABCConfig, make_simulator, run_abc
from repro.epi import engine
from repro.epi.data import (
    US_STATES,
    CountryData,
    get_dataset,
    gravity_mobility,
    haversine_km,
    split_by_population,
    synthetic_dataset,
    usa_states_model,
)
from repro.epi.models import get_model
from repro.epi.spec import EpiModelConfig, identity_mobility, regionalize

R5_POP = (1.0e6, 2.5e5, 4.0e6, 7.5e5, 1.5e6)
R5_A0 = (50.0, 0.0, 20.0, 5.0, 0.0)
R5_R0 = (3.0, 0.0, 1.0, 0.0, 0.0)
DAYS = 12


def _random_mobility(n, seed):
    rows = np.random.default_rng(seed).dirichlet(np.ones(n), size=n)
    return tuple(tuple(float(x) for x in row.astype(np.float32))
                 for row in rows)


@pytest.fixture(scope="module")
def r5():
    spec = regionalize(get_model("metapop_seir"), 5)
    mob = _random_mobility(5, 3)
    ds = synthetic_dataset(
        theta=spec.default_theta, population=R5_POP, num_days=DAYS,
        a0=R5_A0, r0=R5_R0, seed=4, name="r5_geo", model=spec, mobility=mob)
    return spec, ds


def _plain_distances(theta, key, ds, days):
    """The metapop SEIR tau-leap day by day in numpy float32, from the
    equations (S->E beta*S_r*J_r with J_r = sum_q M[r, q] I_q / P_q, the
    prevalence where region r's contacts happen; E->I sigma*E, I->R gamma*I,
    clamped in that order), with the engine's noise stream."""
    theta = np.asarray(theta, np.float32)
    b = theta.shape[0]
    pop = np.asarray(ds.population, np.float32)
    a0 = np.asarray(ds.a0, np.float32)
    r0 = np.asarray(ds.r0, np.float32)
    mob = np.asarray(ds.mobility, np.float32)
    n = pop.size
    beta, sigma, gamma, kappa = (theta[:, k:k + 1] for k in range(4))
    e = kappa * a0
    i = np.broadcast_to(a0, (b, n)).astype(np.float32)
    rec = np.broadcast_to(r0, (b, n)).astype(np.float32)
    s = pop - (e + a0 + r0)
    obs = np.asarray(ds.observed, np.float32).reshape(n, 2, -1)
    acc = np.zeros(b, np.float32)
    for t in range(days):
        z = np.asarray(jax.random.normal(jax.random.fold_in(key, t),
                                         (b, n * 3), jnp.float32))
        z = z.reshape(b, n, 3)
        force = ((i / pop).astype(np.float64) @ mob.T.astype(np.float64)
                 ).astype(np.float32)
        h = [np.maximum(beta * s * force, 0), np.maximum(sigma * e, 0),
             np.maximum(gamma * i, 0)]
        raw = [np.floor(hk + np.sqrt(hk) * z[..., k]) for k, hk in enumerate(h)]
        n_se = np.clip(raw[0], 0, s)
        n_ei = np.clip(raw[1], 0, e)
        n_ir = np.clip(raw[2], 0, i)
        s, e, i, rec = s - n_se, e + n_se - n_ei, i + n_ei - n_ir, rec + n_ir
        acc += (((i - obs[:, 0, t]) ** 2) + ((rec - obs[:, 1, t]) ** 2)).sum(1)
    return np.sqrt(acc)


@pytest.mark.parametrize("backend", ["xla", "xla_fused"])
def test_engine_matches_plain_loop_with_geography(r5, backend):
    spec, ds = r5
    theta = spec.prior().sample(jax.random.PRNGKey(11), (64,))
    key = jax.random.PRNGKey(12)
    cfg = ABCConfig(batch_size=64, chunk_size=64, num_days=DAYS,
                    backend=backend, model=spec)
    got = np.asarray(jax.jit(make_simulator(ds, cfg))(theta, key))
    want = _plain_distances(theta, key, ds, DAYS)
    assert np.isfinite(got).all()
    # numpy sums the coupled mass in another order than XLA's contraction,
    # so a floor() on the edge may move one daily count by one
    np.testing.assert_allclose(got, want, rtol=1e-4)


def test_equal_prevalence_meets_equal_force(r5):
    """Coupling reads prevalence where the contacts happen: at one
    prevalence everywhere, every susceptible meets the same exposure
    hazard beta * I_q / P_q, however the populations and the matrix lean."""
    spec, ds = r5
    pop = np.asarray(R5_POP, np.float32)
    prevalence, beta = 1e-3, 0.6
    rows = np.stack([pop * (1 - prevalence), 0 * pop, pop * prevalence,
                     0 * pop], axis=-1)  # [R, S E I R]
    h = engine.hazards(spec, jnp.asarray(rows.reshape(1, -1)),
                       jnp.asarray([[beta, 0.3, 0.2, 1.0]], jnp.float32),
                       jnp.asarray(pop), mobility=jnp.asarray(ds.mobility))
    per_susceptible = np.asarray(h).reshape(5, 3)[:, 0] / rows[:, 0]
    np.testing.assert_allclose(per_susceptible, beta * prevalence, rtol=1e-5)


def test_geography_reaches_every_region(r5):
    """Per-region populations and seeds are what the day-0 state holds."""
    spec, ds = r5
    theta = jnp.asarray([spec.default_theta], jnp.float32)
    state = np.asarray(engine.initial_state(spec, theta,
                                            ds.model_config())).reshape(5, 4)
    np.testing.assert_array_equal(state[:, 2], R5_A0)  # I
    np.testing.assert_array_equal(state[:, 3], R5_R0)  # R
    np.testing.assert_allclose(state.sum(axis=1), R5_POP)


@pytest.mark.parametrize("backend", ["xla", "xla_fused"])
def test_default_geography_is_bit_identical(backend):
    """P/R in every region, the day-0 counts in the seed region and the
    spec's own ring matrix, carried as a geography, change no bit."""
    spec = get_model("metapop_seir")  # R = 4, ring:0.1
    plain = get_dataset("synthetic_small", num_days=10, model=spec)
    n = spec.n_regions
    zeros = (0.0,) * n
    geo = dataclasses.replace(
        plain, population=(plain.population / n,) * n,
        a0=(plain.a0,) + zeros[1:], r0=(plain.r0,) + zeros[1:], d0=zeros,
        mobility=spec.mobility)
    assert geo.regions == n and plain.regions is None
    theta = spec.prior().sample(jax.random.PRNGKey(8), (32,))
    key = jax.random.PRNGKey(2)
    cfg = ABCConfig(batch_size=32, chunk_size=32, num_days=10,
                    backend=backend, model=spec)
    d_plain = np.asarray(jax.jit(make_simulator(plain, cfg))(theta, key))
    d_geo = np.asarray(jax.jit(make_simulator(geo, cfg))(theta, key))
    np.testing.assert_array_equal(d_plain, d_geo)


def test_mobility_precedence(r5):
    """cfg.mobility, then the dataset's, then the spec's."""
    spec, ds = r5
    theta = spec.prior().sample(jax.random.PRNGKey(1), (32,))
    key = jax.random.PRNGKey(5)

    def dist(dataset, **kw):
        cfg = ABCConfig(batch_size=32, chunk_size=32, num_days=DAYS,
                        model=spec, **kw)
        return np.asarray(jax.jit(make_simulator(dataset, cfg))(theta, key))

    own = dist(ds)
    np.testing.assert_array_equal(own, dist(ds, mobility=ds.mobility))
    ident = dist(ds, mobility=identity_mobility(5))
    assert not np.array_equal(own, ident)
    # without its matrix the dataset takes the spec's (identity here)
    np.testing.assert_array_equal(
        ident, dist(dataclasses.replace(ds, mobility=None)))


def test_geography_validation():
    base = dict(name="bad", d0=0.0, observed=np.zeros((6, 3), np.float32))
    with pytest.raises(ValueError, match="region count"):
        CountryData(population=(1.0, 2.0, 3.0), a0=(1.0, 0.0), r0=0.0, **base)
    with pytest.raises(ValueError, match="row-stochastic"):
        CountryData(population=(1.0, 2.0), a0=1.0, r0=0.0,
                    mobility=((0.5, 0.4), (0.0, 1.0)), **base)
    ds = CountryData(population=np.array([5.0, 6.0]), a0=[1, 0], r0=0.0,
                     **base)
    assert ds.population == (5.0, 6.0) and ds.a0 == (1.0, 0.0)
    assert ds.regions == 2 and ds.mobility is None
    # a geography of another region count fits no spec of this one
    spec3 = regionalize(get_model("metapop_seir"), 3)
    relabelled = dataclasses.replace(ds, observed_channels=spec3.observed_labels)
    assert not relabelled.compatible_with(spec3)


def test_usa_states_invariants():
    spec = regionalize(get_model("metapop_seir"), 51)
    assert spec == usa_states_model()
    ds = get_dataset("usa_states", model=spec)
    assert ds.regions == 51 and ds.observed.shape == (102, 49)
    assert sum(ds.population) == 331_449_281
    assert [p for _, p, _, _ in US_STATES] == list(ds.population)
    assert sum(ds.a0) == 104 and sum(ds.r0) == 7 and ds.d0 == 0.0
    m = np.asarray(ds.mobility, np.float64)
    np.testing.assert_allclose(m.sum(axis=1), 1.0, atol=1e-6)
    np.testing.assert_array_equal(np.diag(m), np.float32(0.96))
    assert (m >= 0).all() and np.isfinite(ds.observed).all()
    assert ds.compatible_with(spec)
    # the national series' model cannot fit it, nor a 4-region spec
    for other in ("siard", "metapop_seir"):
        with pytest.raises(ValueError, match="usa_states"):
            get_dataset("usa_states", model=other)


def test_gravity_and_split_from_their_definitions():
    coords = [(0.0, 0.0), (0.0, 1.0), (0.0, 3.0)]
    d = haversine_km(coords)
    # one degree of longitude on the equator: 2 pi 6371 / 360 km
    assert d[0, 1] == pytest.approx(2 * np.pi * 6371.0 / 360, rel=1e-12)
    pop = (10.0, 20.0, 40.0)
    m = np.asarray(gravity_mobility(pop, coords, b=1.0, c=2.0, eps=0.1))
    w = np.asarray([20.0 / d[0, 1] ** 2, 40.0 / d[0, 2] ** 2])
    np.testing.assert_allclose(m[0, 1:], (0.1 * w / w.sum()).astype(np.float32))
    assert m[1, 1] == np.float32(0.9)
    with pytest.raises(ValueError, match="share a point"):
        gravity_mobility(pop, [(0.0, 0.0)] * 3)
    assert split_by_population(10, (1, 1, 1)) == (4.0, 3.0, 3.0)
    # quotas 3.5, 0.7, 2.8: the two left over go to 0.8 and 0.7
    assert split_by_population(7, (5, 1, 4)) == (3.0, 1.0, 3.0)


def test_run_abc_usa_states_device_loop():
    spec = regionalize(get_model("metapop_seir"), 51)
    ds = get_dataset("usa_states", model=spec)
    base = ABCConfig(batch_size=1000, chunk_size=1000, target_accepted=10,
                     num_days=10, model=spec, wave_loop="device",
                     max_runs=40)
    eps = abc.calibrate_tolerance(ds, base, key=3, quantile=0.01,
                                  n_pilot=2000)
    cfg = dataclasses.replace(base, tolerance=eps)
    post = run_abc(ds, cfg, key=1)
    assert len(post) >= 10 and post.runs < cfg.max_runs
    assert (post.distances <= eps).all()
    # the accepted rows are the dataset's own mobility at work: the same
    # run with the matrix set on the config accepts the same rows
    same = run_abc(ds, dataclasses.replace(cfg, mobility=ds.mobility), key=1)
    np.testing.assert_array_equal(post.theta, same.theta)
    np.testing.assert_array_equal(post.distances, same.distances)


def test_pallas_refuses_a_geography(r5):
    spec, ds = r5
    cfg = ABCConfig(batch_size=128, chunk_size=128, num_days=DAYS,
                    backend="pallas", model=spec)
    with pytest.raises(ValueError, match="geography"):
        make_simulator(ds, cfg)


def test_region_days_counter(r5, monkeypatch):
    spec, ds = r5
    seen = []
    annotation = jax.profiler.TraceAnnotation

    def record(name, **counters):
        seen.append((name, counters))
        return annotation(name, **counters)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", record)
    cfg = ABCConfig(batch_size=64, chunk_size=64, num_days=DAYS, model=spec,
                    tolerance=1e12, target_accepted=100, max_runs=3,
                    wave_loop="device")
    post = run_abc(ds, cfg, key=0)
    harvest = [c for n, c in seen if n == "abc.harvest"]
    assert harvest == [{"sample_days": post.runs * 64 * DAYS,
                        "region_days": post.runs * 64 * DAYS * 5}]
    # the host loop counts the same way, wave by wave
    seen.clear()
    run_abc(ds, dataclasses.replace(cfg, wave_loop="host"), key=0)
    harvest = [c for n, c in seen if n == "abc.harvest"]
    assert harvest and all(
        c["region_days"] == c["sample_days"] * 5 for c in harvest)


def test_region_population_keeps_the_scalar_split():
    spec = regionalize(get_model("metapop_seir"), 3)
    assert engine.region_population(spec, 3e6) == 3e6 / 3
    np.testing.assert_array_equal(
        engine.region_population(spec, (1.0, 2.0, 3.0)), [1.0, 2.0, 3.0])
    with pytest.raises(ValueError, match="3 regions"):
        engine.initial_state(
            spec, jnp.ones((1, 4)),
            EpiModelConfig(population=(1.0, 2.0), num_days=1))


def test_fine_tune_keeps_the_datasets_mobility(monkeypatch):
    """An estimator trained on a geography fine-tunes on its matrix too."""
    from repro.core import npe

    spec = get_model("metapop_seir")  # R = 4
    ds = synthetic_dataset(
        theta=spec.default_theta, population=(4e5, 1e5, 2e5, 3e5),
        num_days=DAYS, a0=(10.0, 0.0, 5.0, 0.0), seed=6, name="r4_geo",
        model=spec, mobility=_random_mobility(4, 9))
    tiny = npe.NPEConfig(train_steps=2, train_batch=32, n_pilot=32,
                         hidden=16, n_components=2, fine_tune_steps=2)
    cfg = ABCConfig(num_days=DAYS, backend="npe", model=spec, npe=tiny)
    seen = []
    make_step = npe._make_train_step

    def record(*args):
        seen.append(np.asarray(args[5]))
        return make_step(*args)

    monkeypatch.setattr(npe, "_make_train_step", record)
    est = npe.train_npe(ds, cfg, key=0)
    tuned = npe.fine_tune(est, ds, key=1)
    assert tuned.train_steps_done == 4 and len(seen) == 2
    for mob in seen:
        np.testing.assert_array_equal(mob, np.asarray(ds.mobility, np.float32))


def test_serving_refuses_a_geography(r5):
    from repro.core.serving import forecast_bands

    spec, ds = r5
    with pytest.raises(ValueError, match="geography"):
        forecast_bands(np.ones((4, 4), np.float32), ds, model=spec,
                       fit_days=DAYS, horizon=3)
