"""Multi-device / multi-pod drivers for parallel ABC (paper §4.5, Table 7).

Two equivalent formulations are provided:

  * `make_pjit_runner`   — GSPMD: one logical batch, sharded over the data
    axes by the partitioner. Simplest; collectives chosen by XLA.
  * `make_shardmap_runner` — explicit per-device program (the faithful analogue
    of the paper's per-IPU replica): each device folds its axis index into the
    run key, simulates its own sub-batch, and the ONLY cross-device collective
    is a psum of the scalar accept count. This is why the paper sees <= 8%
    scaling overhead — we get the same property by construction.

Both return a callable with the RunOutput signature of `abc_run_batch`, so the
host driver (`run_abc`) is oblivious to the device topology. Work addressing
stays (base_key, run_idx, device_idx) => deterministic, resumable, elastic:
a restarted job with a different device count re-partitions runs without
changing the sample stream semantics (each (run, device) pair is a unique
fold_in, and acceptance is i.i.d. across all of them).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.abc import (
    ABCConfig,
    RunOutput,
    SimulatorFn,
    WaveLoopOutput,
    WaveRunner,
    abc_run_batch,
    build_wave_loop,
    make_simulator,
    wave_capacity,
)
from repro.core.priors import UniformBoxPrior, schedule_prior


def make_runner(mesh: Mesh, dataset, cfg: ABCConfig, style: str = "shard_map"):
    """Build a sharded runner from the config alone.

    Resolves the model spec named by `cfg.model` (prior bounds, parameter
    dimension, simulator) so callers never hardcode a particular model's
    shapes. `style` is "shard_map" (paper-faithful per-device replica) or
    "pjit" (GSPMD).
    """
    from repro.epi.models import get_model

    if style not in ("shard_map", "pjit"):
        raise ValueError(f"unknown runner style {style!r}")
    # schedule-aware: theta must carry the scale columns the simulator expects
    prior = schedule_prior(get_model(cfg.model), cfg.schedule)
    simulator = make_simulator(dataset, cfg)
    maker = make_shardmap_runner if style == "shard_map" else make_pjit_runner
    return maker(mesh, prior, simulator, cfg)


def make_wave_runner(mesh: Mesh, dataset, cfg: ABCConfig, style: str = "shard_map"):
    """Sharded DEVICE-RESIDENT wave loop (the multi-device analogue of
    `abc.make_wave_runner`): the whole accept/reject loop stays on the mesh,
    and the host is re-entered only at target/budget/checkpoint boundaries.
    """
    from repro.epi.models import get_model

    if style not in ("shard_map", "pjit"):
        raise ValueError(f"unknown runner style {style!r}")
    # schedule-aware: theta must carry the scale columns the simulator expects
    prior = schedule_prior(get_model(cfg.model), cfg.schedule)
    simulator = make_simulator(dataset, cfg)
    maker = (
        make_shardmap_wave_runner if style == "shard_map" else make_pjit_wave_runner
    )
    return maker(mesh, prior, simulator, cfg)


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """All mesh axes used for ABC data parallelism (every axis: ABC is pure DP)."""
    return tuple(mesh.axis_names)


def make_pjit_runner(
    mesh: Mesh,
    prior: UniformBoxPrior,
    simulator: SimulatorFn,
    cfg: ABCConfig,
) -> Callable[[jax.Array], RunOutput]:
    """GSPMD path: shard the chunk dimension of the global batch."""
    axes = data_axes(mesh)
    run = abc_run_batch(prior, simulator, cfg)
    if cfg.strategy == "outfeed":
        out_shardings = RunOutput(
            NamedSharding(mesh, P(axes)),  # theta [nc, cs, p]
            NamedSharding(mesh, P(axes)),  # dist  [nc, cs]
            NamedSharding(mesh, P(axes)),  # flags [nc]
            NamedSharding(mesh, P()),  # count
        )
    else:
        out_shardings = RunOutput(
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P()),
        )
    return jax.jit(run, out_shardings=out_shardings)


def make_shardmap_runner(
    mesh: Mesh,
    prior: UniformBoxPrior,
    simulator: SimulatorFn,
    cfg: ABCConfig,
) -> Callable[[jax.Array], RunOutput]:
    """Explicit per-device replica; `cfg.batch_size` is the GLOBAL batch.

    Mirrors the paper's setup where "2x100k" means 100k per IPU: the global
    batch is split evenly across every mesh axis.
    """
    axes = data_axes(mesh)
    n_dev = 1
    for a in axes:
        n_dev *= mesh.shape[a]
    if cfg.batch_size % n_dev:
        raise ValueError(f"batch_size {cfg.batch_size} not divisible by {n_dev} devices")
    local_cfg = dataclasses.replace(
        cfg,
        batch_size=cfg.batch_size // n_dev,
        chunk_size=min(cfg.chunk_size, cfg.batch_size // n_dev),
    )
    local_run = abc_run_batch(prior, simulator, local_cfg)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=P(),
        out_specs=RunOutput(P(axes), P(axes), P(axes), P()),
    )
    def run(key: jax.Array) -> RunOutput:
        dev = jax.lax.axis_index(axes)
        out = local_run(jax.random.fold_in(key, dev))
        # The ONLY steady-state collective: scalar accept-count reduction.
        count = jax.lax.psum(out.accept_count, axes)
        if cfg.strategy == "outfeed":
            return RunOutput(out.theta, out.dist, out.chunk_flags, count)
        # topk path: per-device top-k buffers are concatenated along the
        # leading axis by the out_spec; host filters dist <= eps as usual.
        return RunOutput(out.theta, out.dist, out.chunk_flags, count)

    return jax.jit(run)


def effective_chunk_flags(out: RunOutput) -> jax.Array:
    return out.chunk_flags


# --------------------------------------------------------------------------
# Device-resident wave loops, sharded
# --------------------------------------------------------------------------

def make_shardmap_wave_runner(
    mesh: Mesh,
    prior: UniformBoxPrior,
    simulator: SimulatorFn,
    cfg: ABCConfig,
) -> WaveRunner:
    """Per-device replica wave loop: each device runs its own while_loop over
    local waves with a local accept buffer; the ONLY steady-state collective
    is the per-wave psum of the scalar accept count that feeds the shared
    stop condition. Keying matches the legacy shard_map runner exactly:
    wave w on device d draws from fold_in(fold_in(key, run_idx0 + w), d).
    """
    axes = data_axes(mesh)
    n_dev = 1
    for a in axes:
        n_dev *= mesh.shape[a]
    if cfg.batch_size % n_dev:
        raise ValueError(f"batch_size {cfg.batch_size} not divisible by {n_dev} devices")
    local_b = cfg.batch_size // n_dev
    # a device can soak up to (target - 1) of the global accepts plus its own
    # final wave, so the per-shard capacity mirrors the single-device bound
    cap = wave_capacity(cfg, local_b)

    loop = build_wave_loop(
        prior,
        lambda th, k, _data: simulator(th, k),
        cfg,
        batch_size=local_b,
        capacity=cap,
        fold_axis=lambda: jax.lax.axis_index(axes),
        count_all=lambda c: jax.lax.psum(c, axes),
    )

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(axes), P(axes), P(), P(axes), P(), P(), P()),
        out_specs=WaveLoopOutput(P(axes), P(axes), P(), P(), P(axes)),
    )
    def sharded(key, run_idx0, theta_buf, dist_buf, n0, fills, max_waves,
                tolerance, data):
        out = loop(
            key, run_idx0, theta_buf, dist_buf, n0, fills[0], max_waves,
            tolerance, data,
        )
        return out

    def fn(key, run_idx0, theta_buf, dist_buf, n0, fills, max_waves,
           tolerance, data):
        # `data` is always None here (the simulator baked the dataset in);
        # pass a dummy zero so every shard_map input is an array.
        # fills must be rank-1 to satisfy the P(axes) in_spec even on a
        # single-device mesh, where WaveRunner.init hands back a scalar.
        fills = jnp.atleast_1d(jnp.asarray(fills, jnp.int32))
        return sharded(
            key, run_idx0, theta_buf, dist_buf, n0, fills, max_waves,
            tolerance, jnp.zeros((), jnp.int32),
        )

    return WaveRunner(
        fn=jax.jit(fn, donate_argnums=(2, 3)),
        capacity=cap,
        shards=n_dev,
        n_params=prior.dim,
        cfg=cfg,
    )


def make_shardmap_scenario_runner(
    mesh: Mesh,
    prior: UniformBoxPrior,
    sim_call,  # (theta [B_local, p], key, data: ScenarioData) -> dist
    cfg: ABCConfig,
) -> WaveRunner:
    """Per-device-replica wave loop over a PARAMETRIC simulator.

    The campaign's multi-device mode: like `make_shardmap_wave_runner`, but
    the traced `ScenarioData` tuple (observed series, population scalars,
    intervention breakpoints, prior box) rides REPLICATED into every shard
    instead of being baked into the simulator. One compiled loop per
    (scenario shape, device group) therefore still serves every dataset /
    seed / intervention cell of that shape — the compile-reuse property the
    serial campaign relies on, now on a mesh.
    """
    axes = data_axes(mesh)
    n_dev = 1
    for a in axes:
        n_dev *= mesh.shape[a]
    if cfg.batch_size % n_dev:
        raise ValueError(f"batch_size {cfg.batch_size} not divisible by {n_dev} devices")
    local_b = cfg.batch_size // n_dev
    cap = wave_capacity(cfg, local_b)

    loop = build_wave_loop(
        prior,
        sim_call,
        cfg,
        batch_size=local_b,
        capacity=cap,
        fold_axis=lambda: jax.lax.axis_index(axes),
        count_all=lambda c: jax.lax.psum(c, axes),
    )

    @partial(
        jax.shard_map,
        mesh=mesh,
        # the trailing P() is a pytree-prefix spec: every ScenarioData leaf
        # is replicated across the group
        in_specs=(P(), P(), P(axes), P(axes), P(), P(axes), P(), P(), P()),
        out_specs=WaveLoopOutput(P(axes), P(axes), P(), P(), P(axes)),
    )
    def sharded(key, run_idx0, theta_buf, dist_buf, n0, fills, max_waves,
                tolerance, data):
        return loop(
            key, run_idx0, theta_buf, dist_buf, n0, fills[0], max_waves,
            tolerance, data,
        )

    def fn(key, run_idx0, theta_buf, dist_buf, n0, fills, max_waves,
           tolerance, data):
        fills = jnp.atleast_1d(jnp.asarray(fills, jnp.int32))
        return sharded(
            key, run_idx0, theta_buf, dist_buf, n0, fills, max_waves,
            tolerance, data,
        )

    return WaveRunner(
        fn=jax.jit(fn, donate_argnums=(2, 3)),
        capacity=cap,
        shards=n_dev,
        n_params=prior.dim,
        cfg=cfg,
    )


def make_pjit_wave_runner(
    mesh: Mesh,
    prior: UniformBoxPrior,
    simulator: SimulatorFn,
    cfg: ABCConfig,
) -> WaveRunner:
    """GSPMD wave loop: one logical batch per wave, sharded over the mesh by
    sharding hints on the per-wave batch arrays; the accept buffers stay
    replicated. Sample values are identical to the single-device wave loop
    (constraints never change values), so this style is stream-compatible
    with `run_abc`'s default device loop.
    """
    axes = data_axes(mesh)

    def shard_hint(x):
        spec = P(axes, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    loop = build_wave_loop(
        prior,
        lambda th, k, _data: simulator(th, k),
        cfg,
        shard_hint=shard_hint,
    )
    return WaveRunner(
        fn=jax.jit(loop, donate_argnums=(2, 3)),
        capacity=wave_capacity(cfg),
        shards=1,
        n_params=prior.dim,
        cfg=cfg,
    )
