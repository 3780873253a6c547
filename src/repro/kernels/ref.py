"""Pure-jnp oracle for the fused ABC simulation kernel.

Reuses the verified generic engine (`repro.epi.engine`) for the dynamics and
the shared counter-based RNG primitive (`repro.kernels.rng`) for the noise,
so kernel-vs-oracle tests check the kernel's tiling/looping/layout logic
against an independent formulation of the same math — for ANY registered
`CompartmentalModel` spec, not just the paper's SIARD.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.summaries import (
    get_distance_kind,
    get_summary,
    lower_summary,
    pool_channels,
    pool_factor,
    running_day,
    running_finalize,
)
from repro.epi import engine
from repro.epi.spec import CompartmentalModel, EpiModelConfig
from repro.kernels import rng as krng


def hash_normals(
    seed, idx: jax.Array, day, n_transitions: int = 5, slots: int = 8
) -> jax.Array:
    """Noise block [B, n_transitions] for one day from the counter stream.

    For metapop models `n_transitions` is the flattened region-major total
    (R * per-region transitions) and `slots` is `model.ctr_slots`; at R=1
    both collapse to the legacy (n_transitions, 8) layout bit-exactly."""
    cols = []
    for k in range(n_transitions):
        cols.append(krng.normal(seed, idx, krng.day_transition_ctr(day, k, slots)))
    return jnp.stack(cols, axis=-1)


def abc_sim_distance_ref(
    theta: jax.Array,  # [B, n_params (+ n_scales)] f32
    seed,  # uint32 scalar
    observed: jax.Array,  # [n_observed, T] f32
    *,
    population,  # scalar total, or a geography's [R] per-region values
    a0,  # scalar day-0 counts, or a geography's [R] rows (so r0, d0)
    r0,
    d0,
    model: CompartmentalModel | None = None,
    schedule=None,  # InterventionSchedule; theta carries its scale columns
    summary=None,  # SummarySpec / registry name / None (identity)
    distance: str = "euclidean",  # core.summaries.DISTANCE_KINDS name
    mobility=None,  # [R, R] row-stochastic override (metapop models)
) -> jax.Array:
    """Distances [B]: simulate T days with hash RNG, summary distance vs
    observed. Default (identity, euclidean) is the paper's raw Euclidean and
    reduces bit-exactly to the legacy running sum-of-squares; any other pair
    uses the same generalized running accumulator the kernel lowers
    (core.summaries.running_day), pinning kernel-vs-oracle parity per pair.
    A metapop model takes a geography as the engine does: per-region
    populations and day-0 counts, and its matrix as `mobility`."""
    if model is None:
        from repro.epi.models import DEFAULT_MODEL as model  # noqa: N811
    spec = get_summary(summary)
    kind = get_distance_kind(distance)
    lowered = lower_summary(spec, distance, observed, n_regions=model.n_regions)
    pool = pool_factor(spec, model.n_regions)
    mob = engine.mobility_matrix(model, mobility) if model.is_regional else None
    theta = jnp.asarray(theta, jnp.float32)
    batch = theta.shape[0]
    num_days = observed.shape[1]
    cfg = EpiModelConfig(
        population=population, num_days=num_days, a0=a0, r0=r0, d0=d0
    )
    idx = jnp.arange(batch, dtype=jnp.uint32)
    state0 = engine.initial_state(model, theta, cfg)
    obs_by_day = jnp.swapaxes(lowered.obs_summary, 0, 1)  # [T, n_obs]

    obs_idx = model.total_observed_idx

    def step(carry, inp):
        state, cum, binv, acc = carry
        day, obs_t, flush_t = inp
        z = hash_normals(
            seed, idx, day, model.total_transitions, model.ctr_slots
        )  # [B, R * n_trans]
        th_d = engine.effective_theta(model, schedule, theta, day)
        nxt = engine.tau_leap_step(
            model, state, th_d, z, cfg.population, mobility=mob
        )
        cum, binv, acc = running_day(
            spec, kind, lowered.weights,
            pool_channels(nxt[..., obs_idx], pool), obs_t,
            flush_t, cum, binv, acc,
        )
        return (nxt, cum, binv, acc), None

    days = jnp.arange(num_days, dtype=jnp.uint32)
    acc0 = state0[..., 0] * 0.0  # inherits varying mesh axes under shard_map
    chan0 = pool_channels(state0[..., obs_idx], pool) * 0.0
    (state_f, _, _, acc), _ = jax.lax.scan(
        step, (state0, chan0, chan0, acc0), (days, obs_by_day, lowered.flush)
    )
    del state_f
    return running_finalize(kind, lowered.mean_scale, acc)
