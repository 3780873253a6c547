"""Cost model, tuning cache and stream-invariance tests (repro.core.tuning).

Three contracts:

  * the analytic cost model is DERIVED from the model spec — its per-day op
    count cross-checks against a jaxpr count of the full kernels/ref.py
    oracle (same counting currency) for every registered model, and its byte
    model reproduces the seed's hardwired SIARD constants exactly;
  * the tuning cache round-trips, a hit skips all measurement, and corrupt
    caches fail LOUDLY instead of silently retuning;
  * the auto-applied knobs are pure scheduling: accepted sets are
    bit-identical across Pallas tiles, and xla_fused distances are
    bit-identical across scan unroll factors.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tuning
from repro.core.abc import ABCConfig, make_simulator, run_abc
from repro.core.priors import schedule_prior
from repro.epi.data import get_dataset
from repro.epi.models import get_model
from repro.kernels import ref

DAYS = 10


@pytest.fixture(scope="module")
def ds():
    return get_dataset("synthetic_small", num_days=DAYS)


# --------------------------------------------------------------------------
# Cost model: spec-derived, cross-checked against the full oracle trace
# --------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["sir", "seir", "siard"])
def test_cost_model_flops_cross_check_vs_ref(model):
    """The one-day trace behind `cost_model` must agree with a jaxpr count of
    the FULL kernels/ref.py simulation (same currency: count_fn_ops), per
    sample-day, for every registered model — the 'derived from the spec, not
    hardwired' guarantee."""
    spec = get_model(model)
    days, batch = 30, 256
    cm = tuning.cost_model(model, days)
    obs = jnp.ones((spec.n_observed, days), jnp.float32)
    theta = jnp.ones((batch, spec.n_params), jnp.float32)

    def full(th):
        return ref.abc_sim_distance_ref(
            th, jnp.uint32(0), obs,
            population=1e6, a0=100.0, r0=5.0, d0=1.0, model=spec,
        )

    per_sample_day = tuning.count_fn_ops(full, theta) / (batch * days)
    # the full trace adds initial_state + finalize + observed preprocessing,
    # amortized over batch*days — agreement must be tight, not order-of-mag
    np.testing.assert_allclose(per_sample_day, cm.flops_per_sample_day,
                               rtol=0.15)
    assert cm.flops_per_sample_day > 50  # sanity: a real op count, not 0


def test_cost_model_bytes_reproduce_seed_constants():
    """SIARD byte model == the seed's hardwired roofline constants:
    fused 8*4+4 = 36 B/sample, naive (5+3+2*6)*4 = 80 B/sample-day."""
    cm = tuning.cost_model("siard", 49)
    assert cm.fused_bytes_per_sample == 36.0
    assert cm.naive_bytes_per_sample_day == 80.0
    assert cm.theta_width == 8
    # smaller models shrink proportionally (derived, not constant)
    sir = tuning.cost_model("sir", 49)
    assert sir.fused_bytes_per_sample == (sir.theta_width + 1) * 4.0
    assert sir.fused_bytes_per_sample < 36.0


def test_cost_model_schedule_widens_theta():
    from repro.epi.spec import InterventionSchedule

    sched = InterventionSchedule(
        tv_params=("beta",), breakpoints=(10,),
        scale_lows=((0.1,),), scale_highs=((1.0,),),
    )
    base = tuning.cost_model("siard", 49)
    wide = tuning.cost_model("siard", 49, schedule=sched)
    assert wide.theta_width > base.theta_width
    assert wide.fused_bytes_per_sample > base.fused_bytes_per_sample


def test_roofline_fields_shape_and_ceiling():
    cm = tuning.cost_model("siard", 49)
    out = tuning.roofline_metrics(cm, n_samples=1e6, wall_s=1.0)
    assert set(out) == {"achieved_flops", "achieved_bytes_per_s",
                        "arithmetic_intensity", "roofline_efficiency"}
    assert out["achieved_flops"] == pytest.approx(cm.flops(1e6))
    assert 0 < out["roofline_efficiency"] < 1  # CPU-second against TPU peak
    # doubling the wall clock halves achieved flops and efficiency
    slow = tuning.roofline_metrics(cm, n_samples=1e6, wall_s=2.0)
    assert slow["roofline_efficiency"] == pytest.approx(
        out["roofline_efficiency"] / 2
    )


# --------------------------------------------------------------------------
# Tuning cache: round-trip, hit-skips-measurement, loud corruption
# --------------------------------------------------------------------------

def _cfg(**kw):
    base = dict(batch_size=512, chunk_size=512, num_days=DAYS,
                tolerance=1.6e4, target_accepted=5, max_runs=2,
                backend="pallas")
    base.update(kw)
    return ABCConfig(**base)


def test_cache_round_trip(tmp_path):
    path = tmp_path / "cache.json"
    cache = tuning.TuningCache(path)
    assert cache.get("k") is None
    cache.put("k", {"tile": 256})
    assert cache.get("k") == {"tile": 256}
    # a fresh instance reads the persisted file
    assert tuning.TuningCache(path).get("k") == {"tile": 256}
    payload = json.loads(path.read_text())
    assert payload["schema"] == tuning.CACHE_SCHEMA


def test_corrupt_cache_raises_loudly(tmp_path):
    path = tmp_path / "cache.json"
    path.write_text("{not json")
    with pytest.raises(ValueError, match="corrupt tuning cache"):
        tuning.TuningCache(path).get("k")
    path.write_text(json.dumps({"schema": "something-else", "entries": {}}))
    with pytest.raises(ValueError, match="not a tuning-cache/v1"):
        tuning.TuningCache(path).get("k")
    path.write_text(json.dumps({"schema": tuning.CACHE_SCHEMA}))
    with pytest.raises(ValueError, match="not a tuning-cache/v1"):
        tuning.TuningCache(path).get("k")


def test_autotune_hit_skips_measurement(tmp_path, ds):
    cache = tuning.TuningCache(tmp_path / "cache.json")
    cfg = _cfg(autotune=True)
    calls = []

    def fake_measure(c, batch=None):
        calls.append((c.tile, c.scan_unroll, batch))
        return 1.0 if c.tile != 256 else 0.5  # tile 256 "wins"

    entry = tuning.autotune(ds, cfg, cache=cache, measure=fake_measure,
                            measure_batches=False)
    assert calls, "a cache miss must measure"
    assert entry["tile"] == 256
    # a HIT returns the persisted entry without measuring anything
    calls.clear()
    entry2 = tuning.autotune(ds, cfg, cache=cache, measure=fake_measure)
    assert calls == []
    assert entry2 == entry
    # ... even through a fresh cache instance on the same file
    fresh = tuning.TuningCache(tmp_path / "cache.json")
    entry3 = tuning.autotune(ds, cfg, cache=fresh, measure=fake_measure)
    assert calls == [] and entry3["tile"] == 256


def test_autotune_xla_fused_searches_unroll(tmp_path, ds):
    cache = tuning.TuningCache(tmp_path / "cache.json")
    cfg = _cfg(backend="xla_fused", autotune=True)

    def fake_measure(c, batch=None):
        return 0.25 if c.scan_unroll == 4 else 1.0

    entry = tuning.autotune(ds, cfg, cache=cache, measure=fake_measure,
                            measure_batches=False)
    assert entry["scan_unroll"] == 4
    assert "tile" not in entry


def test_resolve_tuned_applies_winner_but_explicit_wins(tmp_path, ds):
    cache = tuning.TuningCache(tmp_path / "cache.json")
    cfg = _cfg(autotune=True)
    cache.put(tuning.cfg_cache_key(cfg),
              {"tile": 256, "scan_unroll": 4, "best_batch": 1024})
    tuned = tuning.resolve_tuned(ds, cfg, cache=cache)
    assert tuned.tile == 256
    assert tuned.autotune is False  # never re-enters the tuner downstream
    assert tuned.batch_size == cfg.batch_size  # best_batch is advisory ONLY
    # an explicit user tile beats the cached winner
    explicit = dataclasses.replace(cfg, tile=128)
    tuned2 = tuning.resolve_tuned(ds, explicit, cache=cache)
    assert tuned2.tile == 128
    # autotune=False is a no-op passthrough
    off = dataclasses.replace(cfg, autotune=False)
    assert tuning.resolve_tuned(ds, off, cache=cache) is off


def test_tile_candidates_respect_divisibility():
    assert tuning.tile_candidates(8192) == (256, 512, 1024, 2048, 4096)
    assert tuning.tile_candidates(512) == (256, 512)
    # nothing divides 300: no explicit candidates (auto would ghost-pad)
    assert tuning.tile_candidates(300) == ()


def test_cache_key_separates_the_tuning_dimensions():
    keys = {
        tuning.cache_key(backend=b, model=m, days=d, batch=n, device_kind=k)
        for k in ("cpu", "TPU v5 lite")
        for b in ("pallas", "xla_fused")
        for m in ("siard", "sir")
        for d in (10, 49)
        for n in (512, 8192)
    }
    assert len(keys) == 32
    # the default key is this process's device: a winner measured on the
    # CPU interpreter is a miss on a chip
    assert tuning.cache_key(backend="pallas", model="siard", days=10,
                            batch=512).startswith(
        f"{jax.devices()[0].device_kind}/")


# --------------------------------------------------------------------------
# Stream invariance of the auto-applied knobs (the safety contract)
# --------------------------------------------------------------------------

def test_accepted_sets_bit_identical_across_tiles(ds):
    """The ISSUE 6 pin: tile is pure scheduling — run_abc on the pallas
    backend must accept the SAME particles (bit-identical theta and
    distances) for every compatible tile."""
    posts = [
        run_abc(ds, _cfg(tile=t), key=0) for t in (128, 256, 512)
    ]
    base = posts[0]
    assert base.simulations > 0
    for p in posts[1:]:
        assert p.simulations == base.simulations
        assert np.array_equal(p.theta, base.theta)
        assert np.array_equal(p.distances, base.distances)


def test_xla_fused_distances_bit_identical_across_unroll(ds):
    theta = schedule_prior(get_model("siard")).sample(
        jax.random.PRNGKey(0), (512,)
    )
    key = jax.random.PRNGKey(1)
    sims = [
        make_simulator(ds, _cfg(backend="xla_fused", scan_unroll=u))
        for u in (1, 4)
    ]
    d1, d4 = (np.asarray(s(theta, key)) for s in sims)
    assert np.array_equal(d1, d4)
