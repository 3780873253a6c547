"""Fused ABC simulation kernel: whole-horizon tau-leap + running distance in VMEM.

This is the TPU-native translation of the paper's IPU insight (DESIGN.md §2):
keep the ENTIRE simulation working set in near-compute memory for the whole
horizon. One `pallas_call` grid cell owns a tile of `TB` samples and:

    state  <- initial_state(theta)                  (VMEM registers)
    for day in 0..T-1:                              (fori_loop, on-chip)
        z      <- counter-based RNG (in-kernel)     (never touches HBM)
        h      <- hazards(state, theta)
        n      <- clamp(floor(h + sqrt(h) * z))     (sequential source drain)
        state  <- state + stoichiometry^T @ n
        acc    += ||state[obs] - obs[:, day]||^2    (running distance)
    dist   <- sqrt(acc)                             (single [TB] HBM write)

Since the stoichiometry-driven refactor the kernel body is generic over any
`CompartmentalModel` spec (repro.epi.spec): the spec's hazard rows, initial
rows, clamp order and stoichiometry are *inlined at trace time* — the Python
loops over transitions/compartments below unroll into straight-line vector
code, exactly like the previous hand-unrolled SIARD body. The spec is a
static (hashable) argument, so each model compiles its own specialized
kernel with VMEM tiles sized from its `n_state` / `n_params`.

HBM traffic per sample: theta's row count in floats (n_params, plus
n_windows*n_tv scale rows under a schedule) + 1 float distance out (36 B for
the paper model unscheduled), versus the naive path's >= (T*n_trans noise +
T*n_obs trajectory + T*n_state state round trips) * 4 B ~ 2.3 KB/sample at
T=49. Arithmetic intensity rises ~60x, which is what moves the workload from
the memory roofline to the compute roofline (EXPERIMENTS.md §Perf, ABC rows).

Data layout: samples ride the 128-lane minor dimension; theta arrives
transposed [n_params_pad, B] (sublane-padded to a multiple of 8) so each
parameter is one (1, TB) VREG row. Under an intervention schedule the theta
block widens to [n_params + n_windows*n_tv, B]: the extra window-major scale
rows are selected per day by unrolled VREG selects against the window index
(breakpoint days arrive as iconst scalars, so they are runtime values — a
lockdown-day sweep reuses one compiled kernel). The n_state channels are
(1, TB) rows carried through the day loop as values (VREGs), not refs.
`TB` is a required tuning knob resolved by `kernels.ops.resolve_tile`
(auto default: 1024 lanes, shrunk to the batch's power-of-two for small
batches) and searched by the measured autotuner (repro.core.tuning) over
{256..4096}; peak VMEM per cell ~ TB/1024 * (n_state + n_params + n_trans
+ 2*n_obs) * 4 KB (the 2*n_obs rows are the summary accumulator's cum/bin
carries), far under the ~16 MB/core budget even at TB=4096, leaving room
for concurrent grid cells. The in-kernel RNG streams are indexed by the
GLOBAL sample index `idx = lane + TB * tile_idx`, so distances — and the
accepted particle sets above them — are bit-identical across tile sizes
(pinned by tests); the tile is pure scheduling.

The observed series lives in SMEM as flat channel-major scalars
(`[n_chan * num_days]`), and each day reads its `n_chan` values as scalar
loads at a dynamic offset, which the scalar unit does natively and which
broadcast to the (1, TB) rows for free. A VMEM block with days on lanes
needs a one-lane slice at a dynamic lane offset, which Mosaic refuses (it
cannot prove the offset 128-aligned); days on sublanes would compile, but
each day would then pay a vector load plus a lane extract and broadcast
per channel. The series is small (at most 20 channels at the R=10 lane
budget, times the horizon), so it fits SMEM whole.

The per-day distance accumulation is the traced-select lowering of the
generalized summary accumulator (repro.core.summaries): the observed block
arrives PRE-SUMMARIZED, and the channel weights / transform selectors /
distance finalizer ride fconst+iconst lanes exactly like the intervention
breakpoints — so a (summary, distance) sweep shares one compiled kernel,
and the default identity+euclidean lanes reproduce the legacy running
Euclidean bit-for-bit.

The kernel returns per-sample distances; accept/compaction stays in XLA
(lax.top_k / chunk flags) because it is O(B) cheap and the paper's two
host-return strategies live above the kernel (core/abc.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.epi import engine
from repro.epi.spec import CompartmentalModel, ScheduleShape
from repro.kernels import rng as krng

# fconsts layout (f32): [population, a0, r0, d0, mean_scale, 0...,
#                        summary channel weights at lanes 8..8+n_chan,
#                        row-major mobility matrix at lanes
#                        8+n_chan..8+n_chan+R*R (metapop models only)]
# iconsts layout (i32): [seed, breakpoint_0..breakpoint_{n_windows-1}, 0...,
#                        summary flags at lanes _SUM_ILANE.._SUM_ILANE+4]
_CONST_LANES = 128
#: sublane granularity for f32 tiles — theta/obs rows are padded to this
_SUBLANES = 8
#: first iconst lane of the summary selector vector (core.summaries.FLAG_*:
#: cumulative, log1p, power, root, bin_days). Selectors and weights are
#: RUNTIME values, exactly like the intervention breakpoints: a summary /
#: distance sweep reuses one compiled kernel (pinned by a jit-cache test).
_SUM_ILANE = 120
#: fconst lane of the distance finalizer's mean scale (1/n_terms or 1.0)
_MEAN_SCALE_LANE = 4
#: first fconst lane of the per-channel summary weights
_WEIGHT_LANE = 8


def n_summary_channels(model: CompartmentalModel, pool: int) -> int:
    """Summary channels the kernel accumulates: the region-major flattened
    observed count, divided by the static region-pooling factor."""
    assert model.total_observed % pool == 0, (model.name, pool)
    return model.total_observed // pool


def mobility_lane(model: CompartmentalModel, pool: int) -> int:
    """First fconst lane of the row-major mobility matrix (after the
    summary channel weights)."""
    return _WEIGHT_LANE + n_summary_channels(model, pool)


def kernel_lane_budget_ok(model: CompartmentalModel, pool: int) -> bool:
    """Whether this model's weights + mobility fit the 128 fconst lanes.

    Metapop models need n_chan weight lanes plus R*R mobility lanes; at
    R=10 with 2 unpooled observed channels that is exactly 128. Larger R
    must route through the XLA backends (ops.abc_sim_distance raises a
    loud ValueError pointing there)."""
    lanes = mobility_lane(model, pool)
    if model.is_regional:
        lanes += model.n_regions * model.n_regions
    return lanes <= _CONST_LANES


def sublane_pad(n: int) -> int:
    """Round a row count up to the f32 sublane tile granularity (min 8)."""
    return max(_SUBLANES, -(-n // _SUBLANES) * _SUBLANES)


def auto_interpret() -> bool:
    """Backend-aware Pallas dispatch: the interpreter is a CPU-only
    correctness fallback — on TPU (and GPU/triton) the kernel must compile.
    """
    return jax.default_backend() == "cpu"


def theta_width(model: CompartmentalModel, sched: ScheduleShape | None) -> int:
    """Rows of the (possibly schedule-widened) transposed theta layout."""
    return model.n_params + (sched.n_scales if sched is not None else 0)


def _kernel(
    theta_ref,
    obs_ref,
    fconst_ref,
    iconst_ref,
    dist_ref,
    *,
    model: CompartmentalModel,
    num_days: int,
    tile: int,
    sched: ScheduleShape | None = None,
    pool: int = 1,
):
    """Generic Pallas kernel body, specialized per model spec. Shapes:
    theta_ref  (Pp, TB)  — params x samples (transposed, sublane-padded);
                           rows n_params.. are window-major intervention
                           scales when `sched` is set
    obs_ref    (n_chan * num_days,) in SMEM — OBSERVED-SIDE SUMMARY values,
                           channel-major (running-bin layout; region-major
                           flattened — or region-pooled when `pool` > 1)
    fconst_ref (1, 128)  — f32 scalars (incl. summary weights / mean scale /
                           row-major mobility matrix for metapop models)
    iconst_ref (1, 128)  — i32 scalars (seed, breakpoint days, summary flags)
    dist_ref   (1, TB)   — output summary distances

    Metapop models carry the region axis as extra UNROLLED (1, TB) rows:
    state/hazard/noise rows are region-major flattened (row r * n + j),
    per-region seeding and population P/R match the XLA engine, the
    mobility-weighted coupled rows are built from fconst mobility lanes, and
    the RNG counter stream widens to `model.ctr_slots` slots per day. At
    R=1 every regional term collapses (one region, slot stride 8, identity
    weights), so single-region kernels stay bit-identical to the pre-metapop
    body — pinned by tests/test_metapop.py.
    """
    population = fconst_ref[0, 0]
    a0 = fconst_ref[0, 1]
    r0 = fconst_ref[0, 2]
    d0 = fconst_ref[0, 3]
    seed = iconst_ref[0, 0].astype(jnp.uint32)
    # breakpoint days ride iconst lanes, so lockdown-day sweeps NEVER
    # recompile the kernel — only the schedule's shape is a compile key
    n_windows = sched.n_windows if sched is not None else 0
    breakpoints = tuple(iconst_ref[0, 1 + i] for i in range(n_windows))
    # summary/distance selectors + weights are runtime lanes too (one
    # compiled kernel serves every (summary, distance) pair): the kernel
    # body below is the traced-select twin of core.summaries.running_day
    mean_scale = fconst_ref[0, _MEAN_SCALE_LANE]
    n_chan = n_summary_channels(model, pool)
    weights = tuple(
        fconst_ref[0, _WEIGHT_LANE + m] for m in range(n_chan)
    )
    cumulative = iconst_ref[0, _SUM_ILANE + 0]
    use_log1p = iconst_ref[0, _SUM_ILANE + 1]
    power = iconst_ref[0, _SUM_ILANE + 2]
    root = iconst_ref[0, _SUM_ILANE + 3]
    bin_days = iconst_ref[0, _SUM_ILANE + 4]

    # region geometry: flat models are the R=1 degenerate case throughout
    R = model.n_regions
    C = model.n_state
    T = model.n_transitions
    slots = model.ctr_slots
    pop_r = population / R if R > 1 else population
    if model.is_regional:
        # mobility rides fconst lanes (row-major), like the breakpoints: a
        # mobility sweep reuses one compiled kernel
        ml = mobility_lane(model, pool)
        mob = tuple(
            tuple(fconst_ref[0, ml + r * R + q] for q in range(R))
            for r in range(R)
        )

    # global sample index of each lane in this tile
    tile_idx = pl.program_id(0)
    lane = jax.lax.broadcasted_iota(jnp.uint32, (1, tile), 1)
    idx = lane + jnp.uint32(tile) * tile_idx.astype(jnp.uint32)

    # theta rows, each (1, TB): base params plus any per-window scale rows
    pc = tuple(
        theta_ref[k : k + 1, :] for k in range(theta_width(model, sched))
    )

    # spec step 1: initial state rows + summary carries (cum/bin per summary
    # channel) + distance accumulator (base params only — interventions scale
    # hazards, never the day-0 seeding). Region `seed_region` receives the
    # dataset's day-0 counts; every other region starts fully susceptible.
    state0 = []
    for r in range(R):
        if R > 1:
            z = 1.0 if r == model.seed_region else 0.0
            rows = model.initial_rows(
                pc[: model.n_params], pop_r, a0 * z, r0 * z, d0 * z
            )
        else:
            rows = model.initial_rows(pc[: model.n_params], pop_r, a0, r0, d0)
        state0.extend(rows)
    acc0 = jnp.zeros_like(state0[0])
    chan0 = tuple(jnp.zeros_like(state0[0]) for _ in range(2 * n_chan))

    obs_idx = model.observed_idx
    ns = R * C

    def day_step(day, carry):
        sc = list(carry[:ns])
        cum = list(carry[ns : ns + n_chan])
        binr = list(carry[ns + n_chan : ns + 2 * n_chan])
        acc = carry[ns + 2 * n_chan]
        # day-effective params: the window selects unroll into straight-line
        # VREG selects (shared row-level code with the XLA engine)
        pc_d = engine.effective_param_rows(model, sched, pc, day, breakpoints)
        new_sc = []
        for r in range(R):
            sc_r = sc[r * C : (r + 1) * C]
            # coupled rows: mobility-weighted compartment mass, appended
            # after the local compartments (spec contract) — unrolls into
            # R multiply-adds per coupled compartment
            extra = []
            for j in model.coupled_idx:
                row = mob[r][0] * sc[j]
                for q in range(1, R):
                    row = row + mob[r][q] * sc[q * C + j]
                extra.append(row)
            # spec step 2: hazards (rates cannot be negative)
            h = [
                jnp.maximum(row, 0.0)
                for row in model.hazard_rows(
                    tuple(sc_r) + tuple(extra), pc_d, pop_r
                )
            ]
            # spec step 3: Gaussian tau-leap counts, generated in-register;
            # counter slot r*T + k in the slots-per-day stream (slot stride
            # 8 and r=0 at R=1 — the legacy layout)
            raw = []
            for k in range(T):
                z = krng.normal(
                    seed, idx, krng.day_transition_ctr(day, r * T + k, slots)
                )
                raw.append(jnp.floor(h[k] + jnp.sqrt(h[k]) * z))
            # spec step 4: sequential source-draining clamp + stoichiometry —
            # shared row-level code with the XLA engine (unrolls at trace
            # time; per-region, the stoichiometry is block-diagonal)
            new_sc.extend(engine.drain_and_apply(model, sc_r, raw))
        sc = new_sc

        # running summary-distance accumulation (beyond-paper fusion,
        # DESIGN.md §2): the traced-select form of summaries.running_day.
        # Identity + euclidean (all-false selects, weights 1.0) is bit-
        # identical to the legacy per-channel squared accumulation.
        # Region-pooled summaries sum each channel across regions here,
        # collapsing the carry to n_obs rows.
        if pool > 1:
            xs = []
            for j in obs_idx:
                x = sc[j]
                for r in range(1, R):
                    x = x + sc[r * C + j]
                xs.append(x)
        else:
            xs = [sc[g] for g in model.total_observed_idx]
        flush = jnp.logical_or(
            (day + 1) % bin_days == 0, day == num_days - 1
        ).astype(jnp.float32)
        for m in range(n_chan):
            x = xs[m]
            c = cum[m] + x
            v = jnp.where(cumulative == 1, c, x)
            # cumulative channels bin by their latest LEVEL, rates by the
            # running within-bin sum (see summaries module docstring)
            b = jnp.where(cumulative == 1, v, binr[m] + v)
            s = jnp.where(use_log1p == 1, jnp.log1p(jnp.maximum(b, 0.0)), b)
            diff = s - obs_ref[m * num_days + day]
            term = jnp.where(power == 1, jnp.abs(diff), diff * diff)
            acc = acc + flush * (weights[m] * term)
            cum[m] = c
            binr[m] = b * (1.0 - flush)
        return (*sc, *cum, *binr, acc)

    carry = jax.lax.fori_loop(0, num_days, day_step, (*state0, *chan0, acc0))
    acc = carry[ns + 2 * n_chan] * mean_scale
    dist_ref[...] = jnp.where(root == 1, jnp.sqrt(acc), acc)


def abc_sim_distance_kernel(
    theta_t: jax.Array,  # [Pp, B] f32 (transposed, sublane-padded, B % tile == 0)
    obs_flat: jax.Array,  # [n_chan * num_days] f32 (channel-major)
    fconsts: jax.Array,  # [1, 128] f32
    iconsts: jax.Array,  # [1, 128] i32 (seed + breakpoint days)
    *,
    model: CompartmentalModel,
    num_days: int,
    tile: int,
    interpret: bool | None = None,
    sched: ScheduleShape | None = None,
    pool: int = 1,
) -> jax.Array:
    """Raw pallas_call wrapper; returns distances [1, B]. See ops.py for the
    user-facing API (padding, layout, backend selection).

    `interpret=None` dispatches by backend (`auto_interpret`): the Python
    interpreter only on CPU, a compiled kernel everywhere else.
    """
    if interpret is None:
        interpret = auto_interpret()
    p_pad, batch = theta_t.shape
    assert p_pad == sublane_pad(theta_width(model, sched)) and batch % tile == 0
    assert obs_flat.shape == (n_summary_channels(model, pool) * num_days,)
    grid = (batch // tile,)
    return pl.pallas_call(
        functools.partial(
            _kernel, model=model, num_days=num_days, tile=tile, sched=sched,
            pool=pool,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((p_pad, tile), lambda i: (0, i)),  # theta tile
            pl.BlockSpec(memory_space=pltpu.SMEM),  # whole observed series
            pl.BlockSpec((1, _CONST_LANES), lambda i: (0, 0)),
            pl.BlockSpec((1, _CONST_LANES), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, batch), jnp.float32),
        interpret=interpret,
    )(theta_t, obs_flat, fconsts, iconsts)
