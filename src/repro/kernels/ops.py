"""Jit'd user-facing wrappers around the Pallas kernels.

`abc_sim_distance` handles layout (transpose, padding), constant packing and
backend selection. On this CPU container interpret=True executes the kernel
body in Python for correctness; on TPU hardware set interpret=False (the
default is auto-detected from the backend).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.summaries import N_FLAGS, get_summary, lower_summary, pool_factor
from repro.epi import engine
from repro.kernels import abc_sim

_CONST_LANES = abc_sim._CONST_LANES


def _auto_interpret() -> bool:
    return abc_sim.auto_interpret()


def resolve_tile(batch: int, tile: int | None = None) -> int:
    """The kernel tile actually used for `batch` — the SINGLE tile authority.

    `tile=None` picks the legacy auto default: 1024 lanes, shrunk to the
    next power of two >= batch for small batches (so a 300-sample pilot run
    pads to one 512-lane cell instead of a mostly-empty 1024-lane one).

    An EXPLICIT tile is taken literally and validated loudly: it must be a
    positive multiple of 128 lanes that divides the batch exactly. The old
    behavior silently clamped the request and over-padded incompatible
    batches, so a tuned `tile=2048` could quietly run at 512 and a
    `batch=300, tile=256` cell could quietly simulate 212 ghost samples —
    invisible in the bench envelope it was supposed to explain.
    """
    if batch <= 0:
        raise ValueError(f"batch must be positive, got {batch}")
    if tile is None:
        return min(1024, max(128, 1 << (batch - 1).bit_length()))
    tile = int(tile)
    if tile < 128 or tile % 128:
        raise ValueError(
            f"tile={tile} is not a positive multiple of 128 lanes; pass "
            "tile=None for the auto default"
        )
    if batch % tile:
        raise ValueError(
            f"tile={tile} does not divide batch={batch}; the kernel would "
            "silently pad {pad} ghost samples. Pick a divisor tile or "
            "tile=None for the auto default".replace(
                "{pad}", str((-batch) % tile)
            )
        )
    return tile


def abc_sim_distance(
    theta: jax.Array,  # [B, n_params (+ n_scales)] f32
    seed: jax.Array,  # uint32 scalar
    observed: jax.Array,  # [n_observed, T] f32
    *,
    population: float,
    a0: float,
    r0: float = 0.0,
    d0: float = 0.0,
    tile: int | None = None,
    interpret: bool | None = None,
    model=None,  # CompartmentalModel spec; defaults to the paper's SIARD
    schedule=None,  # InterventionSchedule; theta carries its scale columns
    breakpoints=None,  # [n_windows] i32 traced override of schedule days
    summary=None,  # SummarySpec / registry name / None (identity)
    distance: str = "euclidean",  # core.summaries.DISTANCE_KINDS name
    mobility=None,  # [R, R] row-stochastic override (metapop models)
) -> jax.Array:
    """Fused simulate+distance for a batch of parameter samples. Returns [B].

    `model` is a static argument of the underlying jitted function: each spec
    compiles its own specialized kernel with the stoichiometry and hazards
    inlined (see kernels/abc_sim). Defaults are resolved HERE, outside the
    jit boundary, so model=None and model=DEFAULT_MODEL share one cache entry.
    Of a `schedule`, only the SHAPE (window count, scaled params) is static:
    breakpoint days are traced i32 scalars, so sweeping lockdown days reuses
    one compiled kernel. The (summary, distance) pair is lowered the same
    way: the observed side is pre-summarized here and the selector flags /
    channel weights / mean scale are traced scalar-lane values, so a summary
    or distance sweep also reuses one compiled kernel (pinned by a jit-cache
    test in tests/test_summaries.py). For metapop models the [R, R] mobility
    matrix rides fconst lanes the same way (a mobility sweep reuses one
    compiled kernel) — which also caps the kernel at roughly R <= 10;
    larger metapop runs must use the XLA backends (loud ValueError here).
    """
    if model is None:
        from repro.epi.models import DEFAULT_MODEL as model  # noqa: N811
    if interpret is None:
        interpret = _auto_interpret()
    # resolve/validate OUTSIDE the jit boundary: tile=None and its resolved
    # value share a cache entry, and bad explicit tiles fail loudly up here
    tile = resolve_tile(int(theta.shape[0]), tile)
    sched = None
    if schedule is not None and not schedule.is_empty:
        sched = schedule.shape(model)
        if breakpoints is None:
            breakpoints = jnp.asarray(schedule.breakpoints, jnp.int32)
    if breakpoints is None:
        breakpoints = jnp.zeros((0,), jnp.int32)
    spec = get_summary(summary)
    pool = pool_factor(spec, model.n_regions)
    if not abc_sim.kernel_lane_budget_ok(model, pool):
        raise ValueError(
            f"model {model.name!r} (R={model.n_regions}, "
            f"{abc_sim.n_summary_channels(model, pool)} summary channels) "
            f"exceeds the kernel's {_CONST_LANES} const-lane budget for "
            "weights + mobility; use backend='xla_fused' (or 'xla') for "
            "large metapop models"
        )
    if model.is_regional:
        mob = engine.mobility_matrix(model, mobility)
    else:
        mob = jnp.zeros((0, 0), jnp.float32)
    lowered = lower_summary(spec, distance, observed, n_regions=model.n_regions)
    return _abc_sim_distance_jit(
        theta, seed, lowered.obs_summary, breakpoints, lowered.weights,
        lowered.mean_scale, lowered.flags, mob, population=population, a0=a0,
        r0=r0, d0=d0, tile=tile, interpret=interpret, model=model, sched=sched,
        pool=pool,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "population", "a0", "r0", "d0", "tile", "interpret", "model", "sched",
        "pool",
    ),
)
def _abc_sim_distance_jit(
    theta: jax.Array,
    seed: jax.Array,
    observed: jax.Array,  # PRE-SUMMARIZED observed side (running-bin layout)
    breakpoints: jax.Array,
    weights: jax.Array,  # [n_chan] f32 summary channel weights
    mean_scale: jax.Array,  # [] f32 distance finalizer scale
    flags: jax.Array,  # [N_FLAGS] i32 summary/distance selectors
    mob: jax.Array,  # [R, R] f32 mobility ([0, 0] for flat models)
    *,
    population: float,
    a0: float,
    r0: float,
    d0: float,
    tile: int,
    interpret: bool,
    model,
    sched,
    pool: int = 1,
) -> jax.Array:
    theta = jnp.asarray(theta, jnp.float32)
    batch, n_params = theta.shape
    width = abc_sim.theta_width(model, sched)
    n_chan = abc_sim.n_summary_channels(model, pool)
    assert n_params == width, (theta.shape, model.name, sched)
    assert observed.shape[0] == n_chan, (observed.shape, model.name, pool)
    num_days = observed.shape[1]
    n_windows = sched.n_windows if sched is not None else 0
    assert breakpoints.shape == (n_windows,), (breakpoints.shape, sched)
    assert weights.shape == (n_chan,), (weights.shape, model.name, pool)
    assert flags.shape == (N_FLAGS,), flags.shape
    n_mob = model.n_regions if model.is_regional else 0
    assert mob.shape == (n_mob, n_mob), (mob.shape, model.name)
    # lane-budget guards: breakpoints grow up from lane 1, summary flags sit
    # at fixed tail lanes, weights (then mobility) live above the four model
    # scalars — abc_sim_distance raises loudly before tracing ever gets here
    assert 1 + n_windows <= abc_sim._SUM_ILANE, n_windows
    assert abc_sim.kernel_lane_budget_ok(model, pool), (model.name, pool)

    # tile arrives pre-resolved (resolve_tile); only an auto tile may pad
    pad_b = (-batch) % tile
    p_pad = abc_sim.sublane_pad(width)
    theta_t = jnp.swapaxes(theta, 0, 1)  # [width, B]
    theta_t = jnp.pad(theta_t, ((0, p_pad - width), (0, pad_b)))

    obs_flat = jnp.asarray(observed, jnp.float32).reshape(-1)

    fconsts = jnp.zeros((1, _CONST_LANES), jnp.float32)
    fconsts = fconsts.at[0, 0].set(population)
    fconsts = fconsts.at[0, 1].set(a0)
    fconsts = fconsts.at[0, 2].set(r0)
    fconsts = fconsts.at[0, 3].set(d0)
    fconsts = fconsts.at[0, abc_sim._MEAN_SCALE_LANE].set(
        jnp.asarray(mean_scale, jnp.float32)
    )
    wl = abc_sim._WEIGHT_LANE
    fconsts = fconsts.at[0, wl : wl + n_chan].set(
        jnp.asarray(weights, jnp.float32)
    )
    if n_mob:
        ml = abc_sim.mobility_lane(model, pool)
        fconsts = fconsts.at[0, ml : ml + n_mob * n_mob].set(
            jnp.asarray(mob, jnp.float32).reshape(-1)
        )
    iconsts = jnp.zeros((1, _CONST_LANES), jnp.int32)
    iconsts = iconsts.at[0, 0].set(jnp.asarray(seed, jnp.uint32).astype(jnp.int32))
    if n_windows:
        iconsts = iconsts.at[0, 1 : 1 + n_windows].set(
            jnp.asarray(breakpoints, jnp.int32)
        )
    sl = abc_sim._SUM_ILANE
    iconsts = iconsts.at[0, sl : sl + N_FLAGS].set(jnp.asarray(flags, jnp.int32))

    dist = abc_sim.abc_sim_distance_kernel(
        theta_t,
        obs_flat,
        fconsts,
        iconsts,
        model=model,
        num_days=num_days,
        tile=tile,
        interpret=interpret,
        sched=sched,
        pool=pool,
    )
    return dist[0, :batch]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "q_block",
                     "kv_block", "interpret"),
)
def flash_attention(
    q: jax.Array,  # [B, S, H, D] (model layout)
    k: jax.Array,  # [B, T, KH, D]
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
    q_block: int = 128,
    kv_block: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """User-facing flash attention: handles layout + padding. Returns the
    model-layout output [B, S, H, D]."""
    from repro.kernels import flash_attention as fa

    if interpret is None:
        interpret = _auto_interpret()
    b, s, h, d = q.shape
    t = k.shape[1]
    qt = jnp.moveaxis(q, 2, 1)  # [B, H, S, D]
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    q_block = min(q_block, max(8, 1 << (s - 1).bit_length()))
    kv_block = min(kv_block, max(8, 1 << (t - 1).bit_length()))
    pad_q = (-s) % q_block
    pad_t = (-t) % kv_block
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_t:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
    out = fa.flash_attention_kernel(
        qt, kt, vt, seq_len=t, causal=causal, window=window, softcap=softcap,
        scale=scale, q_block=q_block, kv_block=kv_block, interpret=interpret,
    )
    return jnp.moveaxis(out[:, :, :s], 1, 2)
