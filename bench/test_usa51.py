"""The `usa51.deep` cell at a CPU size: its plain reference agrees with the
program, the check passes the program and rejects the reference in
bfloat16, and the cell's two per-layer readers read what the program
writes, and nothing where it writes nothing."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference
from bench.test_scopes import BODY, _scoped
from bench.test_tracing import _ctx, _ev, _plane, _recorded
from bench.testing import tiny_run

CELL = "usa51.deep"
#: batch per chip, days and regions of the hand-built trace's fits
B, DAYS, R = 4, 10, 51


def _config(days):
    cell = harness.load_cell(harness.load_benchmark(), CELL)
    return dict(cell.config, num_days=days)


def test_reference_builds_the_programs_gravity_matrix():
    """The reference's own gravity matrix, populations and day-0 split,
    from the configuration alone, are the dataset's, bit for bit."""
    from repro.epi.data import get_dataset, usa_states_model

    config = _config(49)
    ds = get_dataset("usa_states", model=usa_states_model())
    model = reference.load_model(config)
    np.testing.assert_array_equal(model.MOBILITY,
                                  np.asarray(ds.mobility, np.float32))
    np.testing.assert_array_equal(model.POPULATION,
                                  np.asarray(ds.population, np.float32))
    np.testing.assert_array_equal(model.A0, np.asarray(ds.a0, np.float32))
    np.testing.assert_array_equal(model.R0, np.asarray(ds.r0, np.float32))
    assert model.A0.sum() == 104 and model.R0.sum() == 7


def test_reference_split_by_largest_remainder():
    model = reference.load_model(_config(49))
    # quotas 3.5, 0.7, 2.8: the two left over go to 0.8 and 0.7
    np.testing.assert_array_equal(model.largest_remainder(7, [5, 1, 4]),
                                  [3.0, 1.0, 3.0])
    # equal remainders: the earlier region first
    np.testing.assert_array_equal(model.largest_remainder(10, [1, 1, 1]),
                                  [4.0, 3.0, 3.0])


@pytest.mark.parametrize("backend", ["xla", "xla_fused"])
def test_reference_agrees_with_program(backend):
    from repro.core.abc import ABCConfig, make_simulator
    from repro.epi.data import get_dataset, usa_states_model

    days, batch = 10, 256
    spec = usa_states_model()
    ds = get_dataset("usa_states", num_days=days, model=spec)
    ref = reference.Reference(_config(days), ds, jnp.float32)
    key = jax.random.PRNGKey(2024)
    theta, want = ref.batch(key, batch)
    cfg = ABCConfig(batch_size=batch, chunk_size=batch, num_days=days,
                    backend=backend, model=spec)
    _, k_sim = jax.random.split(key)
    got = np.asarray(jax.jit(make_simulator(ds, cfg))(theta, k_sim))
    assert np.isfinite(got).all()
    # the cell's dist_gap limit is 1e-2 of eps; far inside it here
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5)


def test_program_is_correct(tmp_path):
    result, err = tiny_run(tmp_path, workload=CELL, seed=2**31 + 29,
                           seconds=1)
    assert result["correct"], err[-2000:]
    assert result["window_compiles"] == 0
    assert result["attempted"] >= 1 and result["failed"] == 0


def test_bfloat16_control_is_not_correct(tmp_path):
    result, err = tiny_run(tmp_path, workload=CELL, seed=5, seconds=1,
                           control="bfloat16")
    assert not result["correct"], err[-2000:]
    assert [k for k, v in result["checks"].items() if v["value"] > v["limit"]]


def _hand_trace(counters=True, couple=True):
    """One chip, two fits of 3 and 2 waves, times in ns.

    window [0, 1000]; fit 0 [100, 500], fit 1 [600, 950]
    loop ops: prior 20 + 10, simulate 180 + 200 (of which the coupling
    60 + 40), accept 30 + 20; a harvest span outside the fits
    """
    def harvest(start, waves):
        days = waves * B * DAYS
        return _ev("abc.harvest", start, 40, sample_days=days,
                   **({"region_days": days * R} if counters else {}))

    host = {"name": "/host:CPU", "events": [
        _ev("bench.window", 0, 1000),
        _ev("bench.fit", 100, 400, fit=0), harvest(420, 3),
        _ev("bench.fit", 600, 350, fit=1), harvest(900, 2),
        harvest(960, 9),
    ]}
    sim = BODY + "abc.simulate/while/body/closed_call/"
    scope_of = {"fusion.1": BODY + "abc.prior/add",
                "fusion.2": sim + "mul",
                "convolution.3": sim + ("epi.couple/dot_general" if couple
                                        else "dot_general"),
                "fusion.4": BODY + "abc.accept/scatter"}
    prior, rest, dot, accept = ("%fusion.1 = f32[8] fusion(%k)",
                                "%fusion.2 = f32[8] fusion(%t)",
                                "%convolution.3 = f32[8] convolution(%i)",
                                "%fusion.4 = f32[8] fusion(%d)")
    chip = _plane(
        "/device:TPU:0", [("jit_loop(1)", 150, 250), ("jit_loop(1)", 650, 240)],
        [(prior, 150, 20), (rest, 170, 120), (dot, 290, 60),
         (accept, 350, 30),
         (prior, 650, 10), (rest, 660, 160), (dot, 820, 40),
         (accept, 860, 20)])
    return {"planes": [_scoped(chip, scope_of), host]}


def _read(name, ctx):
    return harness.reader("layers", name)(ctx)


def test_hand_trace_readers():
    ctx = _ctx(_hand_trace(), 1, [3, 2])
    assert math.isclose(_read("couple_ms_per_wave", ctx), 100 / 5 / 1e6)
    # simulate 380 ns over (3 + 2) waves x 4 x 10 days x 51 regions
    assert math.isclose(_read("sim_ns_per_region_day", ctx),
                        380 / (5 * B * DAYS * R))


def test_nothing_to_read_gives_nothing():
    assert _read("couple_ms_per_wave",
                 _ctx(_hand_trace(couple=False), 1, [3, 2])) is None
    assert _read("sim_ns_per_region_day",
                 _ctx(_hand_trace(counters=False), 1, [3, 2])) is None
    # the recorded trace of a program without the scope and the counter
    for name in ("couple_ms_per_wave", "sim_ns_per_region_day"):
        assert _read(name, _ctx(_recorded(), 1, [1, 1, 1, 1])) is None


def test_cell_entries():
    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "deep"
    names = {m["name"] for m in harness.metrics_of(bench, CELL, "per_layer")}
    assert names == {"couple_ms_per_wave", "sim_ns_per_region_day"}
    e2e = {m["name"] for m in harness.metrics_of(bench, CELL, "end_to_end")}
    assert e2e == {"sims_per_s", "setup_s"}
    with open(harness.BENCH / "limits" / f"{CELL}.json") as f:
        assert json.load(f) == {"eps_gap": 1e-4, "dist_gap": 1e-2,
                                "set_diff": 0, "stop_gap": 0, "short_fits": 0,
                                "over_eps": 0, "sims_mismatch": 0}
