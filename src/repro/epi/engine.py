"""Generic tau-leap simulation engine over a `CompartmentalModel` spec.

This is the model-agnostic generalization of the paper's §2.1 scheme: a
`lax.scan` over days, each day drawing Gaussian tau-leap transition counts
from the spec's hazards, clamping them with sequential source draining, and
applying the stoichiometry matrix. With the SIARD spec it reproduces the
original hand-unrolled implementation bit-for-bit (same noise layout, same
clamp order, same accumulation order — pinned by tests/test_model_registry).

Four entry points mirror the original module:

  * `simulate`                 — full [B, T, n_state] trajectory
  * `simulate_observed`        — observed channels only, [B, n_obs, T]
  * `simulate_observed_lowmem` — fused simulate + running squared distance
                                 (the beyond-paper memory optimization)
  * `simulate_features`        — simulate + summary FEATURE vectors,
                                 [B, n_features] (the NPE backend's batched
                                 training-pair generator, repro.core.npe)

The Pallas path (`repro.kernels.abc_sim`) inlines the same spec into a fused
VMEM-resident kernel; this module is the paper-faithful XLA reference.

All entry points optionally take an `InterventionSchedule`: theta then
carries extra per-window scale columns and each day's hazards are computed
with that day's window-effective parameters (`effective_param_rows` — the
row-level helper the Pallas kernel shares, like `drain_and_apply`).

Spatial metapopulation specs (`model.is_regional`) take a tensor region
path: state/noise/observed flatten region-major to `[..., R * n]` (see the
spec module docstring), channel rows carry a trailing region axis `[..., R]`
with parameter rows broadcast as `[..., 1]`, and the coupled-mass rows are a
single `[R, R] @ [..., R]` einsum per coupled compartment — so a 100-region
model costs one contraction per day, not an unrolled R^2 expression. The
flat R=1 uncoupled branch is untouched code, keeping every registered model
bit-identical to pre-metapop releases (pinned by tests/test_metapop.py).
An optional traced `mobility` [R, R] override (like the `breakpoints`
override) lets mobility sweeps share one compilation.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.epi.spec import (
    CompartmentalModel,
    EpiModelConfig,
    InterventionSchedule,
    ScheduleShape,
    identity_mobility,
)


def mobility_matrix(model: CompartmentalModel, mobility=None) -> jax.Array:
    """Resolve the [R, R] f32 coupling matrix: a traced override, the spec's
    static matrix, or the identity."""
    mob = model.mobility if mobility is None else mobility
    if mob is None:
        mob = identity_mobility(model.n_regions)
    return jnp.asarray(mob, jnp.float32)


def _seed_vector(model: CompartmentalModel, value) -> jax.Array:
    """[R] day-0 seed counts: `value` in `seed_region`, zero elsewhere."""
    return (
        jnp.zeros((model.n_regions,), jnp.float32)
        .at[model.seed_region]
        .set(jnp.asarray(value, jnp.float32))
    )


def initial_state(
    model: CompartmentalModel, theta: jax.Array, cfg: EpiModelConfig
) -> jax.Array:
    """Spec step 1: theta [..., n_params] -> state [..., total_state].

    Metapop specs seed region `seed_region` with (a0, r0, d0); every other
    region starts fully susceptible at population / R.
    """
    theta = jnp.asarray(theta, jnp.float32)
    if not model.is_regional:
        pc = tuple(theta[..., k] for k in range(model.n_params))
        rows = model.initial_rows(
            pc,
            cfg.population,
            jnp.asarray(cfg.a0, jnp.float32),
            jnp.asarray(cfg.r0, jnp.float32),
            jnp.asarray(cfg.d0, jnp.float32),
        )
        return jnp.stack(list(rows), axis=-1).astype(jnp.float32)
    R, C = model.n_regions, model.n_state
    batch = theta.shape[:-1]
    pc = tuple(theta[..., k : k + 1] for k in range(model.n_params))
    rows = model.initial_rows(
        pc,
        cfg.population / R,
        _seed_vector(model, cfg.a0),
        _seed_vector(model, cfg.r0),
        _seed_vector(model, cfg.d0),
    )
    rows = [
        jnp.broadcast_to(jnp.asarray(r, jnp.float32), batch + (R,)) for r in rows
    ]
    # [..., R, C] -> region-major flat [..., R*C]
    return jnp.stack(rows, axis=-1).reshape(batch + (R * C,)).astype(jnp.float32)


def effective_param_rows(
    model: CompartmentalModel,
    shape: Optional[ScheduleShape],
    pc: Sequence,
    day,
    breakpoints: Sequence,
):
    """Apply an intervention schedule's window scales to parameter rows.

    `pc` holds the widened parameter channels (n_params base rows followed by
    window-major scale rows); `day` is a (traced) scalar day index and
    `breakpoints` a sequence of n_windows (traced or concrete) scalar days.
    Returns the n_params EFFECTIVE rows for that day: window 0 is the base
    parameters untouched, window w >= 1 multiplies each time-varying
    parameter by its scale row.

    Row-level like `drain_and_apply`, so the SAME code runs in the XLA
    engine (rows are [...] slices) and inside the Pallas kernel body (rows
    are (1, TB) VREGs); the Python loops unroll at trace time into
    straight-line selects — the schedule never adds control flow.
    """
    if shape is None or shape.n_windows == 0:
        return tuple(pc[: model.n_params])
    day = jnp.asarray(day, jnp.int32)
    w = jnp.zeros((), jnp.int32)  # window index: #{breakpoints <= day}
    for b in breakpoints:
        w = w + (day >= jnp.asarray(b, jnp.int32)).astype(jnp.int32)
    out = list(pc[: model.n_params])
    for j, pi in enumerate(shape.tv_indices):
        scale = jnp.ones_like(out[pi])  # window 0: base params, scale 1
        for win in range(shape.n_windows):
            row = pc[model.n_params + win * shape.n_tv + j]
            scale = jnp.where(w == win + 1, row, scale)
        out[pi] = out[pi] * scale
    return tuple(out)


def effective_theta(
    model: CompartmentalModel,
    schedule: Optional[InterventionSchedule],
    theta: jax.Array,
    day,
    breakpoints=None,
) -> jax.Array:
    """Tensor-layout wrapper: widened theta [..., n_params + n_scales] ->
    day-effective theta [..., n_params]. `breakpoints` optionally overrides
    the schedule's static days with traced scalars (campaign sweeps)."""
    if schedule is None or schedule.is_empty:
        return theta
    shape = schedule.shape(model)
    bp = schedule.breakpoints if breakpoints is None else breakpoints
    width = schedule.param_width(model)
    pc = tuple(theta[..., k] for k in range(width))
    rows = effective_param_rows(model, shape, pc, day, bp)
    return jnp.stack(list(rows), axis=-1)


def _breakpoint_scalars(schedule, breakpoints):
    """Resolve the per-window breakpoint scalars for the scan helpers."""
    if schedule is None or schedule.is_empty:
        return ()
    if breakpoints is None:
        return schedule.breakpoints
    bp = jnp.asarray(breakpoints, jnp.int32)
    return tuple(bp[i] for i in range(schedule.n_windows))


def hazards(
    model: CompartmentalModel,
    state: jax.Array,
    theta: jax.Array,
    population: float,
    mobility=None,
) -> jax.Array:
    """Transition rates: state [..., total_state] -> h [..., total_transitions].

    Metapop specs evaluate all regions at once: channel rows carry a trailing
    region axis, parameters broadcast as [..., 1] rows, and each coupled
    compartment contributes one mobility-weighted mass row via a single
    [R, R] contraction. `mobility` optionally overrides the spec's static
    matrix with a traced [R, R] value (mobility sweeps share one compile).
    """
    if not model.is_regional:
        sc = tuple(state[..., k] for k in range(model.n_state))
        pc = tuple(theta[..., k] for k in range(model.n_params))
        h = jnp.stack(list(model.hazard_rows(sc, pc, population)), axis=-1)
        # Hazards are rates of counting processes; they cannot be negative.
        return jnp.maximum(h, 0.0)
    R, C, T = model.n_regions, model.n_state, model.n_transitions
    batch = state.shape[:-1]
    st = state.reshape(batch + (R, C))
    sc = tuple(st[..., k] for k in range(C))  # each [..., R]
    pc = tuple(theta[..., k : k + 1] for k in range(model.n_params))
    mob = mobility_matrix(model, mobility)
    # full f32: a TPU runs f32 contractions in one bf16 pass by default,
    # which would blur the coupled mass to ~3 significant digits
    coupled = tuple(
        jnp.einsum("rq,...q->...r", mob, st[..., j],
                   precision=jax.lax.Precision.HIGHEST)
        for j in model.coupled_idx
    )
    rows = model.hazard_rows(sc + coupled, pc, population / R)
    h = jnp.stack(
        [jnp.broadcast_to(r, batch + (R,)) for r in rows], axis=-1
    )  # [..., R, T]
    return jnp.maximum(h, 0.0).reshape(batch + (R * T,))


def drain_and_apply(model: CompartmentalModel, sc, raw_counts):
    """Clamp raw transition-count rows and apply the stoichiometry matrix.

    Transitions are clamped in declaration order with sequential source
    draining: each clamp is bounded by what its source compartment still has
    after earlier transitions out of the same source. Guarantees
    non-negativity and exact mass conservation for any spec.

    Operates on channel rows (`sc`: one array per compartment, `raw_counts`:
    one per transition) so the SAME code serves this XLA engine and the
    Pallas kernel body — the mass-conservation-critical logic exists once.
    Returns the next-state rows.
    """
    sc = list(sc)
    remaining = {}  # source compartment -> undrained budget
    counts = []
    for k, src in enumerate(model.transition_sources):
        avail = remaining.get(src, sc[src])
        n_k = jnp.clip(raw_counts[k], 0.0, avail)
        remaining[src] = avail - n_k
        counts.append(n_k)
    for k, row in enumerate(model.stoichiometry):
        for j, coef in enumerate(row):
            if coef == 1:
                sc[j] = sc[j] + counts[k]
            elif coef == -1:
                sc[j] = sc[j] - counts[k]
    return sc


def apply_transitions(
    model: CompartmentalModel, state: jax.Array, n_raw: jax.Array
) -> jax.Array:
    """Tensor-layout wrapper around `drain_and_apply`.

    Metapop specs drain per region: the channel/count rows carry a trailing
    region axis, so the shared row-level clamp logic applies unchanged.
    """
    if not model.is_regional:
        sc = (state[..., k] for k in range(model.n_state))
        raw = [n_raw[..., k] for k in range(model.n_transitions)]
        return jnp.stack(drain_and_apply(model, sc, raw), axis=-1)
    R, C, T = model.n_regions, model.n_state, model.n_transitions
    batch = state.shape[:-1]
    st = state.reshape(batch + (R, C))
    nr = n_raw.reshape(batch + (R, T))
    sc = (st[..., k] for k in range(C))
    raw = [nr[..., k] for k in range(T)]
    out = drain_and_apply(model, sc, raw)  # rows [..., R]
    return jnp.stack(out, axis=-1).reshape(batch + (R * C,))


def tau_leap_step(
    model: CompartmentalModel,
    state: jax.Array,
    theta: jax.Array,
    noise: jax.Array,
    population: float,
    mobility=None,
) -> jax.Array:
    """One day of tau-leaping given standard-normal noise
    [..., total_transitions] (region-major: slot r * n_transitions + k is
    region r's transition k).

    n_k = floor(h_k + sqrt(h_k) * z_k), clamped to sources (paper steps 2-4).
    """
    h = hazards(model, state, theta, population, mobility)
    n_raw = jnp.floor(h + jnp.sqrt(h) * noise)
    return apply_transitions(model, state, n_raw)


def simulate(
    model: CompartmentalModel,
    theta: jax.Array,
    key: jax.Array,
    cfg: EpiModelConfig,
    schedule: Optional[InterventionSchedule] = None,
    breakpoints=None,
    mobility=None,
) -> jax.Array:
    """Full state trajectory [B, T, total_state] (state *after* each day's
    update; region-major channels for metapop specs, reshape with
    `regional_view` for an explicit [B, R, T, n_state] axis).

    Noise is drawn with jax.random (threefry) — the paper-faithful path.
    With a `schedule`, theta is the widened [..., n_params + n_scales] layout
    and each day's hazards use that day's window-effective parameters; the
    noise stream is unchanged, and schedule=None stays bit-identical to the
    constant-theta path.
    """
    theta = jnp.asarray(theta, jnp.float32)
    batch_shape = theta.shape[:-1]
    state0 = initial_state(model, theta, cfg)
    bp = _breakpoint_scalars(schedule, breakpoints)

    def step(state, day):
        # Per-day fold_in keeps this bit-identical with the fused low-memory
        # path (simulate_observed_lowmem) for the same key.
        z = jax.random.normal(
            jax.random.fold_in(key, day),
            batch_shape + (model.total_transitions,),
            jnp.float32,
        )
        th_d = effective_theta(model, schedule, theta, day, bp)
        nxt = tau_leap_step(model, state, th_d, z, cfg.population, mobility)
        return nxt, nxt

    _, traj = jax.lax.scan(step, state0, jnp.arange(cfg.num_days))
    # traj: [T, B, total_state] -> [B, T, total_state]
    return jnp.moveaxis(traj, 0, -2)


def regional_view(series: jax.Array, model: CompartmentalModel) -> jax.Array:
    """Unflatten the region-major channel axis: [..., R*n, T] -> [..., R, n, T]
    (works for observed series and, with n = n_state, state trajectories
    transposed channel-major)."""
    R = model.n_regions
    n = series.shape[-2] // R
    return series.reshape(series.shape[:-2] + (R, n) + series.shape[-1:])


def simulate_observed(
    model: CompartmentalModel,
    theta: jax.Array,
    key: jax.Array,
    cfg: EpiModelConfig,
    schedule: Optional[InterventionSchedule] = None,
    breakpoints=None,
    mobility=None,
) -> jax.Array:
    """Observed channels only: [B, total_observed, T] (the paper's D_s
    layout; metapop channels flatten region-major, channel r*n_obs + m)."""
    traj = simulate(model, theta, key, cfg, schedule, breakpoints, mobility)
    obs = traj[..., model.total_observed_idx]  # [B, T, total_obs]
    return jnp.swapaxes(obs, -1, -2)  # [B, total_obs, T]


def simulate_observed_lowmem(
    model: CompartmentalModel,
    theta: jax.Array,
    key: jax.Array,
    cfg: EpiModelConfig,
    observed: jax.Array,
    schedule: Optional[InterventionSchedule] = None,
    breakpoints=None,
    summary=None,
    distance: str = "euclidean",
    unroll: int = 1,
    mobility=None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused simulate + running summary-distance accumulation.

    The beyond-paper memory optimization (DESIGN.md §2): never materialize
    the [B, n_obs, T] trajectory; fold the (summary, distance) pair into the
    day scan via the generalized running accumulator (core.summaries).
    Returns (distance [B], final state).

    `summary` is a SummarySpec / registry name / None; the default
    (identity, "euclidean") reduces to exactly the legacy running
    sum-of-squares — flush and weights are constant 1.0 and every transform
    select is constant-false, so outputs stay bit-identical to pre-summary
    releases (pinned by tests/test_summaries.py).

    This is the pure-XLA analogue of the Pallas kernel; the kernel
    additionally keeps the whole loop in VMEM.
    """
    from repro.core.summaries import (
        get_distance_kind,
        get_summary,
        lower_summary,
        pool_channels,
        pool_factor,
        running_day,
        running_finalize,
    )

    spec = get_summary(summary)
    kind = get_distance_kind(distance)
    lowered = lower_summary(spec, distance, observed, n_regions=model.n_regions)
    pool = pool_factor(spec, model.n_regions)
    theta = jnp.asarray(theta, jnp.float32)
    batch_shape = theta.shape[:-1]
    obs_idx = model.total_observed_idx
    state0 = initial_state(model, theta, cfg)
    # derive from state0 so the carries inherit its varying mesh axes when
    # this runs inside shard_map (scan carries must have uniform vma types)
    acc0 = state0[..., 0] * 0.0
    # [..., n_chan] cum/bin carries (region-pooled channels pool the sims)
    chan0 = pool_channels(state0[..., obs_idx], pool) * 0.0
    obs_by_day = jnp.swapaxes(lowered.obs_summary, 0, 1)  # [T, n_chan]
    bp = _breakpoint_scalars(schedule, breakpoints)

    def step(carry, inp):
        state, cum, binv, acc = carry
        day, obs_t, flush_t = inp
        z = jax.random.normal(
            jax.random.fold_in(key, day),
            batch_shape + (model.total_transitions,),
            jnp.float32,
        )
        th_d = effective_theta(model, schedule, theta, day, bp)
        nxt = tau_leap_step(model, state, th_d, z, cfg.population, mobility)
        cum, binv, acc = running_day(
            spec, kind, lowered.weights,
            pool_channels(nxt[..., obs_idx], pool), obs_t, flush_t,
            cum, binv, acc,
        )
        return (nxt, cum, binv, acc), None

    days = jnp.arange(cfg.num_days)
    # `unroll` is the xla_fused chunking knob searched by the autotuner
    # (repro.core.tuning): pure scheduling, the day streams are unchanged so
    # distances stay bit-identical across unroll factors (pinned by tests)
    (state_f, _, _, acc_f), _ = jax.lax.scan(
        step, (state0, chan0, chan0, acc0), (days, obs_by_day, lowered.flush),
        unroll=max(1, int(unroll)),
    )
    return running_finalize(kind, lowered.mean_scale, acc_f), state_f


def simulate_features(
    model: CompartmentalModel,
    theta: jax.Array,
    key: jax.Array,
    cfg: EpiModelConfig,
    schedule: Optional[InterventionSchedule] = None,
    breakpoints=None,
    summary=None,
    mobility=None,
) -> jax.Array:
    """Simulate + summary feature vectors: [B, p] theta -> [B, n_features].

    The batched training-pair generator of the NPE backend (repro.core.npe):
    one call yields a device-resident batch of `(theta, x)` pairs where
    `x = summary_features(summary, simulate_observed(theta))` — the same
    summary values the ABC running accumulator compares, flattened to the
    flush-day columns (core.summaries.summary_features). Noise streams are
    the paper-faithful `simulate` streams, so a feature batch under a given
    key is reproducible across runs and backends.
    """
    from repro.core.summaries import get_summary, summary_features

    sim = simulate_observed(model, theta, key, cfg, schedule, breakpoints,
                            mobility)
    return summary_features(get_summary(summary), sim, model.n_regions)
