"""Device-resident wave loop: same-seed equivalence with the host loop.

The contract pinned here is the acceptance criterion of the device loop: for
the same (key, config), the device-resident lax.while_loop driver must
produce the IDENTICAL accepted-sample set — same samples, same order, same
run count — as the legacy per-wave host loop, on the "xla" and "xla_fused"
backends, for every registered model.
"""

import dataclasses
import glob
import os
import re
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.abc import (
    COMPACT_ROWS as K,
    ABCConfig,
    ABCState,
    build_wave_loop,
    compact_accepted,
    make_simulator,
    make_wave_runner,
    run_abc,
    wave_capacity,
)
from repro.epi.data import get_dataset
from repro.epi.models import get_model, list_models
from repro.launch.mesh import make_auto_mesh

DAYS = 12


def _model_tolerance(model: str, backend: str = "xla_fused") -> float:
    """Per-model epsilon at a ~2% pilot acceptance rate (models have very
    different distance scales; a hardcoded epsilon would accept nothing or
    everything depending on the model)."""
    ds = get_dataset("synthetic_small", num_days=DAYS, model=model)
    cfg = ABCConfig(batch_size=1024, num_days=DAYS, chunk_size=1024,
                    backend=backend, model=model)
    sim = jax.jit(make_simulator(ds, cfg))
    th = get_model(model).prior().sample(jax.random.PRNGKey(99), (1024,))
    d = np.asarray(sim(th, jax.random.PRNGKey(98)))
    return float(np.quantile(d[np.isfinite(d)], 0.02))


def _cfg(model: str, backend: str, tol: float, **kw) -> ABCConfig:
    base = dict(
        batch_size=1024, tolerance=tol, target_accepted=20, chunk_size=128,
        strategy="outfeed", max_runs=10, num_days=DAYS, backend=backend,
        model=model,
    )
    base.update(kw)
    return ABCConfig(**base)


@pytest.mark.parametrize("model", list_models())
@pytest.mark.parametrize("backend", ["xla", "xla_fused"])
def test_device_loop_identical_to_host_loop(model, backend):
    tol = _model_tolerance(model, "xla_fused")
    ds = get_dataset("synthetic_small", num_days=DAYS, model=model)
    p_host = run_abc(ds, _cfg(model, backend, tol, wave_loop="host"), key=0)
    p_dev = run_abc(ds, _cfg(model, backend, tol, wave_loop="device"), key=0)
    assert len(p_dev) == len(p_host) > 0
    assert p_dev.runs == p_host.runs
    assert p_dev.simulations == p_host.simulations
    np.testing.assert_array_equal(p_host.theta, p_dev.theta)
    np.testing.assert_array_equal(p_host.distances, p_dev.distances)


def test_device_loop_budget_exhaustion_identical():
    """With an unreachable target both drivers must burn the same wave budget
    and keep every accepted sample (including sub-target harvests)."""
    tol = _model_tolerance("siard")
    ds = get_dataset("synthetic_small", num_days=DAYS)
    kw = dict(target_accepted=10**6, max_runs=4)
    # 10**6 target forces the host fallback in auto mode — request explicitly
    p_host = run_abc(ds, _cfg("siard", "xla_fused", tol, wave_loop="host", **kw),
                     key=3)
    p_dev = run_abc(ds, _cfg("siard", "xla_fused", tol, wave_loop="device", **kw),
                    key=3)
    assert p_host.runs == p_dev.runs == 4
    np.testing.assert_array_equal(p_host.theta, p_dev.theta)


def test_device_loop_checkpoint_resume_identical():
    """Segmented (checkpointing) and interrupted+resumed device runs must
    reproduce the uninterrupted accepted set exactly."""
    tol = _model_tolerance("siard")
    ds = get_dataset("synthetic_small", num_days=DAYS)
    cfg = _cfg("siard", "xla_fused", tol, target_accepted=40, max_runs=20,
               wave_loop="device")
    p_full = run_abc(ds, cfg, key=7)

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "wave_state.npz")
        # segmented run: checkpoint every 2 waves
        p_seg = run_abc(ds, cfg, key=7, checkpoint_every=2, checkpoint_path=path)
        np.testing.assert_array_equal(p_full.theta, p_seg.theta)

        # interrupted at a small budget, then resumed to the full budget
        cfg_cut = dataclasses.replace(cfg, max_runs=2)
        st = ABCState()
        run_abc(ds, cfg_cut, key=7, state=st, checkpoint_every=1,
                checkpoint_path=path)
        resumed = ABCState.load(path)
        assert resumed.run_idx == st.run_idx
        p_res = run_abc(ds, cfg, key=7, state=resumed)
        assert len(p_res) == len(p_full)
        np.testing.assert_array_equal(p_full.theta, p_res.theta)


def test_auto_mode_picks_device_for_outfeed():
    from repro.core.abc import _auto_device_loop

    assert _auto_device_loop(ABCConfig(strategy="outfeed"))
    assert not _auto_device_loop(ABCConfig(strategy="topk"))
    assert not _auto_device_loop(ABCConfig(strategy="outfeed", wave_loop="host"))
    # absurd buffer sizes fall back to the host loop in auto mode only
    big = ABCConfig(strategy="outfeed", target_accepted=10**9)
    assert not _auto_device_loop(big)
    assert _auto_device_loop(dataclasses.replace(big, wave_loop="device"))


def test_wave_capacity_never_overflows():
    """fill <= capacity by construction: entering a wave requires
    accepted < target, and a wave adds at most one batch."""
    cfg = ABCConfig(batch_size=512, target_accepted=10, tolerance=np.inf,
                    chunk_size=512, num_days=DAYS, max_runs=3)
    ds = get_dataset("synthetic_small", num_days=DAYS)
    prior = get_model("siard").prior()
    runner = make_wave_runner(prior, make_simulator(ds, cfg), cfg)
    carry = runner.init(ABCState(n_params=prior.dim))
    out = runner(jax.random.PRNGKey(0), 0, carry, 3)
    # everything accepted (eps = inf): one wave overshoots to a full batch
    assert int(out.n_accepted) == 512
    assert int(out.waves_done) == 1
    assert int(out.fill_counts[0]) == 512 <= wave_capacity(cfg)


def test_pjit_wave_runner_matches_single_device_stream():
    """GSPMD wave-loop style: sharding hints must not change sample values."""
    from repro.core.distributed import make_wave_runner as make_dist_wave_runner

    tol = _model_tolerance("siard")
    ds = get_dataset("synthetic_small", num_days=DAYS)
    cfg = _cfg("siard", "xla_fused", tol)
    # sharding hints need Auto axes (jax.make_mesh defaults to Explicit)
    mesh = make_auto_mesh((len(jax.devices()),), ("data",))
    wr = make_dist_wave_runner(mesh, ds, cfg, style="pjit")
    p_pjit = run_abc(ds, cfg, key=0, wave_runner=wr)
    p_single = run_abc(ds, cfg, key=0)
    np.testing.assert_array_equal(p_single.theta, p_pjit.theta)


# ------------------------------------------------------------------------
# Accept-buffer edge cases: compact_accepted semantics at the capacity edge
# ------------------------------------------------------------------------

def _buffers(capacity, p=2, fill=0):
    th = np.full((capacity, p), -1.0, np.float32)
    d = np.full((capacity,), np.inf, np.float32)
    return jnp.asarray(th), jnp.asarray(d), jnp.int32(fill)


def test_compact_accepted_zero_accepts_is_a_noop():
    """An all-reject wave must leave the buffers bitwise untouched."""
    from repro.core.abc import compact_accepted

    cap, B, p = 8, 4, 2
    th_buf, d_buf, fill = _buffers(cap, p, fill=3)
    theta = jnp.arange(B * p, dtype=jnp.float32).reshape(B, p)
    dist = jnp.arange(B, dtype=jnp.float32)
    accept = jnp.zeros((B,), bool)
    th2, d2, fill2 = compact_accepted(th_buf, d_buf, fill, theta, dist,
                                      accept, cap)
    assert int(fill2) == 3
    np.testing.assert_array_equal(np.asarray(th2), np.asarray(th_buf))
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(d_buf))


def test_compact_accepted_fills_capacity_exactly():
    """fill + accepts == capacity: every accepted row lands, in order, and
    the buffer reports exactly full."""
    from repro.core.abc import compact_accepted

    cap, B, p = 6, 4, 2
    th_buf, d_buf, fill = _buffers(cap, p, fill=2)
    theta = jnp.arange(B * p, dtype=jnp.float32).reshape(B, p)
    dist = jnp.asarray([10.0, 11.0, 12.0, 13.0], jnp.float32)
    accept = jnp.asarray([True, True, True, True])
    th2, d2, fill2 = compact_accepted(th_buf, d_buf, fill, theta, dist,
                                      accept, cap)
    assert int(fill2) == cap
    np.testing.assert_array_equal(np.asarray(d2)[2:], np.asarray(dist))
    np.testing.assert_array_equal(np.asarray(th2)[2:], np.asarray(theta))
    # pre-existing rows untouched
    np.testing.assert_array_equal(np.asarray(d2)[:2], np.inf)


def test_compact_accepted_overflow_drops_excess_keeps_prefix():
    """More accepts than free slots: the first (capacity - fill) accepted
    rows land in stream order, the excess is dropped by the scatter, and the
    returned fill OVERCOUNTS (callers clamp with min(fill, capacity) — the
    WaveLoopOutput contract)."""
    from repro.core.abc import compact_accepted

    cap, B, p = 4, 6, 2
    th_buf, d_buf, fill = _buffers(cap, p, fill=2)
    theta = jnp.arange(B * p, dtype=jnp.float32).reshape(B, p)
    dist = jnp.arange(10.0, 10.0 + B, dtype=jnp.float32)
    accept = jnp.asarray([True, False, True, True, True, False])  # 4 accepts
    th2, d2, fill2 = compact_accepted(th_buf, d_buf, fill, theta, dist,
                                      accept, cap)
    # 2 free slots -> accepted samples 0 and 2 land; 3 and 4 are dropped
    np.testing.assert_array_equal(np.asarray(d2)[2:], [10.0, 12.0])
    np.testing.assert_array_equal(
        np.asarray(th2)[2:], np.asarray(theta)[[0, 2]]
    )
    assert int(fill2) == 2 + 4  # overcount by design
    assert min(int(fill2), cap) == cap


def _plain_scatter(th_buf, d_buf, fill, theta, dist, accept, capacity):
    """The accept-buffer contract, row by row: accepted rows in stream
    order from `fill` on, those past `capacity` dropped, every accept
    counted."""
    th, d = np.array(th_buf), np.array(d_buf)
    for rank, i in enumerate(np.flatnonzero(accept)):
        if fill + rank < capacity:
            th[fill + rank], d[fill + rank] = theta[i], dist[i]
    return th, d, fill + int(np.sum(accept))


#: (id, batch, p, accepts, fill) into a buffer of _CAP rows, on either
#: side of the count and capacity edges that choose the branch; the batch
#: of 300 is not a multiple of the 128-row chunks
_CAP = 320
_CASES = (
    [(f"accepts={n}", 300, 8, n, 5) for n in (0, 1, K - 1, K, K + 1, 300)]
    + [(f"fill+K=cap{d:+d}", 300, 2, 3, _CAP - K + d) for d in (-1, 0, 1)]
    + [("p=2,accepts=K", 300, 2, K, 5),
       ("fill>cap", 300, 8, 3, _CAP + 7),
       ("batch<K", 64, 8, 10, 5),
       ("batch<K,overflow", 64, 8, 40, _CAP - 20)])


@pytest.mark.parametrize("B,p,n_accept,fill", [c[1:] for c in _CASES],
                         ids=[c[0] for c in _CASES])
def test_compact_accepted_matches_a_plain_scatter(B, p, n_accept, fill):
    """Bounded window or full scatter, the buffers and fill are bitwise
    those of the contract."""
    rng = np.random.default_rng(n_accept * 1009 + fill)
    accept = np.zeros(B, bool)
    accept[rng.choice(B, n_accept, replace=False)] = True
    theta = rng.standard_normal((B, p)).astype(np.float32)
    dist = rng.standard_normal(B).astype(np.float32)
    th_buf = rng.standard_normal((_CAP, p)).astype(np.float32)
    d_buf = rng.standard_normal(_CAP).astype(np.float32)
    got = jax.jit(compact_accepted, static_argnums=6)(
        th_buf, d_buf, jnp.int32(fill), theta, dist, accept, _CAP)
    want = _plain_scatter(th_buf, d_buf, fill, theta, dist, accept, _CAP)
    assert int(got[2]) == want[2]
    for g, w in zip(got[:2], want[:2]):
        np.testing.assert_array_equal(np.asarray(g).view(np.uint32),
                                      w.view(np.uint32))


def test_wave_loop_one_wave_over_the_bound_falls_back(small_dataset):
    """A wave of COMPACT_ROWS + 1 accepts into a COMPACT_ROWS-row buffer
    takes the full scatter: the first COMPACT_ROWS accepted rows land in
    stream order, fill_counts is clamped and n_accepted counts them all."""
    B = 2 * K + 44
    cfg = ABCConfig(batch_size=B, target_accepted=10**6, chunk_size=B,
                    num_days=15, max_runs=2)
    prior = get_model("siard").prior()
    # a permutation of 0..B-1 as the distance: rows at most K are accepted
    perm = (np.arange(B) * 7919) % B
    loop = jax.jit(build_wave_loop(
        prior, lambda th, k, _d: jnp.asarray(perm, jnp.float32), cfg,
        capacity=K))
    th0 = jnp.zeros((K, prior.dim), jnp.float32)
    d0 = jnp.full((K,), jnp.inf, jnp.float32)
    key = jax.random.PRNGKey(3)
    out = loop(key, 0, th0, d0, 0, 0, 1, np.float32(K), None)
    assert int(out.waves_done) == 1
    assert int(out.n_accepted) == K + 1
    assert int(out.fill_counts[0]) == K
    # wave 0 draws theta from the first half of fold_in(key, 0)
    theta = prior.sample(jax.random.split(jax.random.fold_in(key, 0))[0],
                         (B,))
    rows = np.flatnonzero(perm <= K)[:K]
    np.testing.assert_array_equal(np.asarray(out.dist_buf), perm[rows])
    np.testing.assert_array_equal(np.asarray(out.theta_buf),
                                  np.asarray(theta)[rows])


def test_wave_loop_single_wave_overflow_reports_clamped_fill(small_dataset):
    """A capacity-capped loop whose single wave over-accepts must clamp
    fill_counts to capacity while n_accepted counts every acceptance."""
    from repro.core.abc import build_wave_loop, make_simulator
    from repro.epi.models import get_model

    B = 256
    cfg = ABCConfig(batch_size=B, tolerance=np.inf, target_accepted=10**6,
                    chunk_size=B, num_days=15, max_runs=2)
    prior = get_model("siard").prior()
    sim = make_simulator(small_dataset, cfg)
    cap = B // 2  # deliberately too small: one all-accept wave overflows
    loop = jax.jit(build_wave_loop(
        prior, lambda th, k, _d: sim(th, k), cfg, capacity=cap))
    th0 = jnp.zeros((cap, prior.dim), jnp.float32)
    d0 = jnp.full((cap,), jnp.inf, jnp.float32)
    out = loop(jax.random.PRNGKey(0), 0, th0, d0, 0, 0, 1, np.inf, None)
    assert int(out.waves_done) == 1
    assert int(out.n_accepted) == B  # every sample accepted (eps = inf)
    assert int(out.fill_counts[0]) == cap  # clamped to the buffer
    assert bool(jnp.all(jnp.isfinite(out.dist_buf)))  # fully populated


def test_wave_capacity_reaches_exactly_full(small_dataset):
    """target == capacity via an explicit override: the loop stops when the
    buffer is exactly full, with every row valid."""
    from repro.core.abc import build_wave_loop, make_simulator
    from repro.epi.models import get_model

    B = 128
    cfg = ABCConfig(batch_size=B, tolerance=np.inf, target_accepted=2 * B,
                    chunk_size=B, num_days=15, max_runs=4)
    prior = get_model("siard").prior()
    sim = make_simulator(small_dataset, cfg)
    cap = 2 * B  # two all-accept waves fill it to the brim, exactly
    loop = jax.jit(build_wave_loop(
        prior, lambda th, k, _d: sim(th, k), cfg, capacity=cap))
    th0 = jnp.zeros((cap, prior.dim), jnp.float32)
    d0 = jnp.full((cap,), jnp.inf, jnp.float32)
    out = loop(jax.random.PRNGKey(0), 0, th0, d0, 0, 0, 4, np.inf, None)
    assert int(out.waves_done) == 2
    assert int(out.n_accepted) == 2 * B
    assert int(out.fill_counts[0]) == cap
    assert bool(jnp.all(jnp.isfinite(out.dist_buf)))


def test_shardmap_wave_runner_matches_host_distributed_stream():
    """Per-device-replica wave loop vs the legacy shard_map host loop: the
    union of accepted samples must match (ordering differs across shards)."""
    from repro.core.distributed import (
        make_runner,
        make_wave_runner as make_dist_wave_runner,
    )

    tol = _model_tolerance("siard")
    ds = get_dataset("synthetic_small", num_days=DAYS)
    cfg = _cfg("siard", "xla_fused", tol)
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    p_host = run_abc(ds, cfg, key=0, run_fn=make_runner(mesh, ds, cfg))
    wr = make_dist_wave_runner(mesh, ds, cfg, style="shard_map")
    p_dev = run_abc(ds, cfg, key=0, wave_runner=wr)
    assert len(p_host) == len(p_dev)
    np.testing.assert_array_equal(
        np.sort(p_host.distances), np.sort(p_dev.distances)
    )


# ------------------------------------------------------------------------
# Instrumentation: device scopes in the wave loop, host spans in the driver
# ------------------------------------------------------------------------

WAVE_SCOPES = ("abc.prior", "abc.simulate", "abc.accept")


def _scopes_in(hlo_text: str) -> set:
    """Named scopes of WAVE_SCOPES that some op_name of the text holds."""
    return {part for name in re.findall(r'op_name="([^"]*)"', hlo_text)
            for part in name.split("/") if part in WAVE_SCOPES}


def _compiled_loop_text(runner) -> str:
    th, d, n0, fill0 = runner.init(ABCState(n_params=runner.n_params))
    return runner.fn.lower(
        jax.random.PRNGKey(0), np.int32(0), th, d, n0, fill0, np.int32(2),
        np.float32(runner.cfg.tolerance), runner.data,
    ).compile().as_text()


@pytest.mark.parametrize("backend", ["xla_fused", "pallas"])
def test_compiled_wave_loop_carries_step_scopes(backend):
    ds = get_dataset("synthetic_small", num_days=DAYS)
    cfg = _cfg("siard", backend, 1e6, batch_size=1024, chunk_size=1024)
    runner = make_wave_runner(get_model("siard").prior(),
                              make_simulator(ds, cfg), cfg)
    assert _scopes_in(_compiled_loop_text(runner)) == set(WAVE_SCOPES)


def test_sharded_wave_loop_carries_step_scopes():
    from conftest import run_in_subprocess

    code = f"""
import re, jax, numpy as np
from repro.core import distributed
from repro.core.abc import ABCConfig, ABCState
from repro.core.scaling import device_mesh
from repro.epi.data import get_dataset

assert len(jax.devices()) == 4
ds = get_dataset("synthetic_small", num_days={DAYS})
cfg = ABCConfig(batch_size=1024, chunk_size=256, num_days={DAYS},
                tolerance=1e6, target_accepted=20, max_runs=4)
wr = distributed.make_wave_runner(device_mesh(4), ds, cfg, style="shard_map")
th, d, n0, fills = wr.init(ABCState(n_params=wr.n_params))
text = wr.fn.lower(jax.random.PRNGKey(0), np.int32(0), th, d, n0, fills,
                   np.int32(2), np.float32(cfg.tolerance), None
                   ).compile().as_text()
names = re.findall(r'op_name="([^"]*)"', text)
print(sorted({{p for n in names for p in n.split("/") if p.startswith("abc.")}}))
"""
    out = run_in_subprocess(code, n_devices=4)
    # 256 rows a chip: the compaction's full-scatter branch is in the loop
    assert out.strip().splitlines()[-1] == str(
        sorted(WAVE_SCOPES + ("abc.accept_fallback",)))


def _host_spans(trace_dir) -> list:
    """(name, start_ns, end_ns, stats) of the host spans of a trace."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return sorted(
        [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
         for plane in ProfileData.from_file(path).planes
         if plane.name.startswith("/host:CPU")
         for line in plane.lines for e in line.events
         if e.name == "fit" or e.name.startswith("abc.")],
        key=lambda s: (s[1], -s[2]))


def test_fit_spans_nest_in_order_with_counters(tmp_path):
    ds = get_dataset("synthetic_small", num_days=DAYS)
    cfg = _cfg("siard", "xla_fused", _model_tolerance("siard"))
    runner = make_wave_runner(get_model("siard").prior(),
                              make_simulator(ds, cfg), cfg)
    run_abc(ds, cfg, key=0, wave_runner=runner)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("fit"):
            post = run_abc(ds, cfg, key=1, wave_runner=runner)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    assert [s[0] for s in spans] == ["fit", "abc.init", "abc.wave_loop",
                                     "abc.harvest", "abc.posterior"]
    fit, init, loop, harvest, posterior = spans
    assert all(fit[1] <= s[1] and s[2] <= fit[2] for s in spans[1:])
    assert all(a[2] <= b[1] for a, b in zip(spans[1:], spans[2:]))
    assert init[3] == loop[3] == posterior[3] == {}
    assert harvest[3] == {
        "sample_days": post.runs * cfg.batch_size * cfg.num_days}


@pytest.mark.parametrize("wave_loop,phases", [
    ("device", {"init", "wave_loop", "harvest", "posterior"}),
    ("host", {"wave_loop", "harvest", "posterior"}),
])
def test_phase_record_within_wall_time(wave_loop, phases):
    ds = get_dataset("synthetic_small", num_days=DAYS)
    cfg = _cfg("siard", "xla_fused", _model_tolerance("siard"),
               wave_loop=wave_loop)
    post = run_abc(ds, cfg, key=0)
    assert set(post.phase_s) == phases
    assert all(v > 0 for v in post.phase_s.values())
    assert sum(post.phase_s.values()) <= post.wall_time_s
