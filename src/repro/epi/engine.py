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

The engine's logic is row-level: `hazard_rows`, `drain_and_apply` and
`effective_param_rows` take one array per compartment, transition or
parameter, the layout the Pallas kernel body uses too. The fused scan of
`simulate_observed_lowmem` carries its state as those rows (`[B]` each, or
`[B, R]` for a regional spec) and stacks it once, after the last day. The
tensor wrappers `hazards`, `apply_transitions` and `tau_leap_step` stack and
split around the rows; they serve `simulate` (the `xla` backend's
trajectory), `simulate_features`, the kernel oracle and the tests.

All entry points optionally take an `InterventionSchedule`: theta then
carries extra per-window scale columns and each day's hazards are computed
with that day's window-effective parameters (`effective_param_rows` — the
row-level helper the Pallas kernel shares, like `drain_and_apply`).

Spatial metapopulation specs (`model.is_regional`) take a tensor region
path: state/noise/observed tensors flatten region-major to `[..., R * n]`
(see the spec module docstring), channel rows carry a trailing region axis
`[..., R]` with parameter rows broadcast as `[..., 1]`, and the coupled-mass
rows are a single `[R, R] @ [..., R]` einsum per coupled compartment — so a
100-region model costs one contraction per day, not an unrolled R^2
expression. Every registered R = 1 model stays bit-identical to
pre-metapop releases (pinned by tests/test_metapop.py).
An optional traced `mobility` [R, R] override (like the `breakpoints`
override) lets mobility sweeps share one compilation. A geography (a
length-R population and length-R day-0 counts in the `EpiModelConfig`)
replaces the even split population / R and the single seeded region, and
couples each region to the prevalence x_q / P_q of the places its contacts
happen (`coupling_matrix`).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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


def _region_vector(model: CompartmentalModel, value, what: str) -> jax.Array:
    """A geography's [R] per-region values as f32, checked against R."""
    vec = jnp.asarray(value, jnp.float32)
    if vec.shape != (model.n_regions,):
        raise ValueError(
            f"{what} has shape {vec.shape}; model {model.name!r} has "
            f"{model.n_regions} regions"
        )
    return vec


def region_population(model: CompartmentalModel, population):
    """People in each region: a length-R population (a geography) is per
    region as it stands; a scalar total is split evenly, population / R."""
    if np.ndim(population):
        return _region_vector(model, population, "population")
    return population / model.n_regions


def coupling_matrix(model: CompartmentalModel, mobility,
                    population) -> jax.Array:
    """The [R, R] matrix a coupled row contracts with.

    Without a geography it is the mobility M: coupled_r = sum_q M[r, q] x_q,
    every region holding the same P/R. With a geography's per-region
    populations, region r's contacts in q meet q's prevalence x_q / P_q;
    the coupled row carries that prevalence at region r's own scale,
    P_r * sum_q M[r, q] x_q / P_q, so a hazard's x / P_r reads
    sum_q M[r, q] x_q / P_q. The matrix is then M[r, q] P_r / P_q, which is
    M itself, bit for bit, where the populations are equal.
    """
    mob = mobility_matrix(model, mobility)
    if not np.ndim(population):
        return mob
    pop = region_population(model, population)
    return mob * (pop[:, None] / pop[None, :])


def _seed_vector(model: CompartmentalModel, value) -> jax.Array:
    """[R] day-0 seed counts: a length-R value (a geography) as it stands,
    a scalar in `seed_region` with zero elsewhere."""
    if np.ndim(value):
        return _region_vector(model, value, "day-0 counts")
    return (
        jnp.zeros((model.n_regions,), jnp.float32)
        .at[model.seed_region]
        .set(jnp.asarray(value, jnp.float32))
    )


def initial_state_rows(
    model: CompartmentalModel, theta: jax.Array, cfg: EpiModelConfig
) -> tuple:
    """Spec step 1 as compartment rows: theta [..., n_params] -> C rows, each
    [...] for an R = 1 spec or [..., R] for a regional one.

    Metapop specs take a geography where `cfg` carries one: a length-R
    population and length-R day-0 counts are per region. Without one, region
    `seed_region` is seeded with the scalar (a0, r0, d0) and every region
    holds population / R, the others fully susceptible.
    """
    theta = jnp.asarray(theta, jnp.float32)
    shape = theta.shape[:-1]
    if not model.is_regional:
        rows = model.initial_rows(
            param_rows(model, theta),
            cfg.population,
            jnp.asarray(cfg.a0, jnp.float32),
            jnp.asarray(cfg.r0, jnp.float32),
            jnp.asarray(cfg.d0, jnp.float32),
        )
    else:
        shape = shape + (model.n_regions,)
        rows = model.initial_rows(
            param_rows(model, theta),
            region_population(model, cfg.population),
            _seed_vector(model, cfg.a0),
            _seed_vector(model, cfg.r0),
            _seed_vector(model, cfg.d0),
        )
    return tuple(
        jnp.broadcast_to(jnp.asarray(r, jnp.float32), shape) for r in rows
    )


def stack_state(model: CompartmentalModel, rows) -> jax.Array:
    """C compartment rows -> the state tensor [..., total_state], flattened
    region-major for a regional spec (channel r * n_state + c)."""
    state = jnp.stack(list(rows), axis=-1)
    if not model.is_regional:
        return state
    return state.reshape(state.shape[:-2] + (model.total_state,))


def initial_state(
    model: CompartmentalModel, theta: jax.Array, cfg: EpiModelConfig
) -> jax.Array:
    """Spec step 1: theta [..., n_params] -> state [..., total_state]
    (`initial_state_rows`, stacked)."""
    return stack_state(model, initial_state_rows(model, theta, cfg))


def param_rows(model: CompartmentalModel, theta: jax.Array, width=None):
    """Split theta [..., width] into parameter rows: [...] each at R = 1,
    [..., 1] for a regional spec (they broadcast over the region axis).
    `width` defaults to n_params; a schedule's widened theta passes its
    `param_width`."""
    width = model.n_params if width is None else width
    if not model.is_regional:
        return tuple(theta[..., k] for k in range(width))
    return tuple(theta[..., k : k + 1] for k in range(width))


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


def hazard_rows(
    model: CompartmentalModel, sc, pc, population, mobility=None
) -> list:
    """Row-level transition rates: compartment rows `sc` and parameter rows
    `pc` (`param_rows` layout) -> one clamped hazard row per transition,
    each the shape of a compartment row.

    Regional specs append one mobility-weighted mass row per coupled
    compartment, each a single [R, R] contraction of that compartment's
    [..., R] row (`coupling_matrix`) under the named scope `epi.couple`.
    """
    sc = tuple(sc)
    shape = sc[0].shape
    if model.is_regional:
        # full f32: a TPU runs f32 contractions in one bf16 pass by default,
        # which would blur the coupled mass to ~3 significant digits
        with jax.named_scope("epi.couple"):
            mob = coupling_matrix(model, mobility, population)
            coupled = tuple(
                jnp.einsum("rq,...q->...r", mob, sc[j],
                           precision=jax.lax.Precision.HIGHEST)
                for j in model.coupled_idx
            )
        sc = sc + coupled
        population = region_population(model, population)
    # Hazards are rates of counting processes; they cannot be negative.
    return [
        jnp.maximum(jnp.broadcast_to(h, shape), 0.0)
        for h in model.hazard_rows(sc, pc, population)
    ]


def state_rows(model: CompartmentalModel, state: jax.Array) -> tuple:
    """The state tensor [..., total_state] -> C compartment rows (the
    inverse of `stack_state`)."""
    if model.is_regional:
        state = state.reshape(
            state.shape[:-1] + (model.n_regions, model.n_state))
    return tuple(state[..., k] for k in range(model.n_state))


def hazards(
    model: CompartmentalModel,
    state: jax.Array,
    theta: jax.Array,
    population: float,
    mobility=None,
) -> jax.Array:
    """Transition rates: state [..., total_state] -> h [..., total_transitions]
    (region-major: slot r * n_transitions + k is region r's transition k).

    The tensor wrapper around `hazard_rows`. `mobility` optionally overrides
    the spec's static matrix with a traced [R, R] value (mobility sweeps
    share one compile); `population` is a scalar total or a geography's [R]
    per-region row (`region_population`), which couples prevalence in place
    of mass.
    """
    rows = hazard_rows(model, state_rows(model, state),
                       param_rows(model, theta), population, mobility)
    h = jnp.stack(rows, axis=-1)
    if not model.is_regional:
        return h
    return h.reshape(h.shape[:-2] + (model.total_transitions,))


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
    T = model.n_transitions
    if model.is_regional:
        n_raw = n_raw.reshape(n_raw.shape[:-1] + (model.n_regions, T))
    raw = [n_raw[..., k] for k in range(T)]
    return stack_state(
        model, drain_and_apply(model, state_rows(model, state), raw))


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


def _noise_row(model: CompartmentalModel, z: jax.Array, k: int) -> jax.Array:
    """Transition k's row of a day's noise z [..., total_transitions]: the
    column k at R = 1, the strided slots r * n_transitions + k of every
    region for a regional spec (the numbers `tau_leap_step` reads)."""
    if not model.is_regional:
        return z[..., k]
    T = model.n_transitions
    return jax.lax.slice_in_dim(z, k, z.shape[-1], stride=T, axis=z.ndim - 1)


def _observed_channels(model: CompartmentalModel, rows, pool: int):
    """One day's observed channels from compartment rows: [..., n_obs] at
    R = 1 or where the summary pools the regions (summed over them, as
    `core.summaries.pool_channels` does), else [..., R, n_obs], whose
    row-major order is the region-major `total_observed_idx` order."""
    obs = jnp.stack([rows[c] for c in model.observed_idx], axis=-1)
    if model.is_regional and pool > 1:
        return jnp.sum(obs, axis=-2)
    return obs


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
    Returns (distance [B], final state [B, total_state]).

    The scan carries the state as C compartment rows ([B] each, [B, R] for
    a regional spec) and works on rows throughout: `hazard_rows`, the
    tau-leap floor per transition row, `drain_and_apply`, theta split into
    parameter rows once before the scan, observed channels picked by static
    index. No day stacks or re-splits the state or the hazards; only the
    observed rows are stacked, into the distance. Every element gets the
    operations of `simulate`'s tensor path in the same order, and the noise
    is the same [B, total_transitions] draw sliced per transition, so the
    final state equals `simulate(...)[:, -1]` bit for bit (pinned by
    tests/test_scan_layout.py).

    `summary` is a SummarySpec / registry name / None; the default
    (identity, "euclidean") reduces to exactly the legacy running
    sum-of-squares — flush and weights are constant 1.0 and every transform
    select is constant-false (pinned by tests/test_summaries.py).

    This is the pure-XLA analogue of the Pallas kernel; the kernel
    additionally keeps the whole loop in VMEM.
    """
    from repro.core.summaries import (
        get_distance_kind,
        get_summary,
        lower_summary,
        pool_factor,
        running_day,
        running_finalize,
    )

    spec = get_summary(summary)
    kind = get_distance_kind(distance)
    lowered = lower_summary(spec, distance, observed, n_regions=model.n_regions)
    pool = pool_factor(spec, model.n_regions)
    theta = jnp.asarray(theta, jnp.float32)
    noise_shape = theta.shape[:-1] + (model.total_transitions,)
    if schedule is None or schedule.is_empty:
        sched, width = None, model.n_params
    else:
        sched, width = schedule.shape(model), schedule.param_width(model)
    pc = param_rows(model, theta, width)
    rows0 = initial_state_rows(model, theta, cfg)
    # derive from the state so the carries inherit its varying mesh axes when
    # this runs inside shard_map (scan carries must have uniform vma types)
    acc0 = (rows0[0][..., 0] if model.is_regional else rows0[0]) * 0.0
    # [..., n_chan] cum/bin carries (region-pooled channels pool the sims)
    chan0 = _observed_channels(model, rows0, pool) * 0.0
    obs_by_day = jnp.swapaxes(lowered.obs_summary, 0, 1)  # [T, n_chan]
    weights = lowered.weights
    if model.is_regional and pool == 1:
        # unpooled regional channels stay [R, n_obs]: no day interleaves
        # the observed rows into the flat region-major layout
        chan = (model.n_regions, model.n_observed)
        obs_by_day = obs_by_day.reshape((cfg.num_days,) + chan)
        weights = weights.reshape(chan)
    bp = _breakpoint_scalars(schedule, breakpoints)

    def step(carry, inp):
        rows, cum, binv, acc = carry
        day, obs_t, flush_t = inp
        z = jax.random.normal(
            jax.random.fold_in(key, day), noise_shape, jnp.float32)
        pd = effective_param_rows(model, sched, pc, day, bp)
        h = hazard_rows(model, rows, pd, cfg.population, mobility)
        raw = [
            jnp.floor(hk + jnp.sqrt(hk) * _noise_row(model, z, k))
            for k, hk in enumerate(h)
        ]
        rows = tuple(drain_and_apply(model, rows, raw))
        cum, binv, acc = running_day(
            spec, kind, weights, _observed_channels(model, rows, pool),
            obs_t, flush_t, cum, binv, acc,
        )
        return (rows, cum, binv, acc), None

    days = jnp.arange(cfg.num_days)
    # `unroll` is the xla_fused chunking knob searched by the autotuner
    # (repro.core.tuning): pure scheduling, the day streams are unchanged so
    # distances stay bit-identical across unroll factors (pinned by tests)
    (rows_f, _, _, acc_f), _ = jax.lax.scan(
        step, (rows0, chan0, chan0, acc0), (days, obs_by_day, lowered.flush),
        unroll=max(1, int(unroll)),
    )
    return running_finalize(kind, lowered.mean_scale, acc_f), stack_state(
        model, rows_f)


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
