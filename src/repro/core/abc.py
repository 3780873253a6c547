"""Massively parallel ABC rejection sampling (paper §3).

The paper's algorithm, verbatim in structure:

  repeat until `target_accepted` samples accepted:
    theta  ~ prior, vectorized          [B, p]
    D_s    ~ simulator(theta)           [B, n_obs, T]  (or fused distance;
                                        n_obs = 3 for the paper's SIARD)
    dist   = ||D_s - D||                [B]
    accept = dist <= tolerance
    return samples to host under a *fixed-shape* strategy (XLA constraint):
      - "outfeed" (paper's IPU path): split the batch into chunks; a chunk is
        transferred to host only if it contains >= 1 accepted sample.
      - "topk"    (paper's GPU path): return the k lowest-distance samples per
        run plus the global accept count; host filters dist <= eps.

Everything device-side is a single jitted function with static output shapes.
In JAX the "transfer only flagged chunks" semantics fall out naturally:
outputs are device arrays, and the host calls `jax.device_get` ONLY on the
flagged chunk rows, so D2H traffic matches the paper's outfeed behaviour.

Two wave-loop drivers share the per-wave math:

  * host loop   — one jitted wave per call; the host harvests RunOutput after
    every wave (the original paper-faithful structure).
  * device loop — a single jitted `lax.while_loop` that runs simulate ->
    compare -> compact-into-buffer for as many waves as needed, with donated
    fixed-size accept buffers, and returns to the host only once the
    acceptance target is met or the wave budget is exhausted. Same-seed
    accepted-sample sets are identical to the host loop (pinned by
    tests/test_wave_loop.py); the per-wave host sync disappears.

The engine is resumable (ABCState) and backend-pluggable:
  backend="xla"        paper-faithful full-trajectory simulate + distance
  backend="xla_fused"  running-distance scan (no [B, n_obs, T] tensor)
  backend="pallas"     fused VMEM-resident Pallas kernel (repro.kernels)
  backend="npe"        amortized neural posterior estimation (repro.core.npe):
                       no waves at all — a mixture-density estimator trained
                       once on simulator output answers queries with a single
                       forward pass. `run_abc` delegates to `npe.run_npe`;
                       the wave machinery below never runs for this backend.

Every wave backend accepts every registered (summary, distance) pair
(ABCConfig.summary / ABCConfig.distance, see repro.core.summaries): the
"xla" path applies the summary post hoc, "xla_fused" folds it into the
running scan, and "pallas" lowers it into the kernel's per-day accumulator
with the weights/selectors riding scalar const lanes. The default
(identity, euclidean) is bit-identical to pre-summary releases.
"""

from __future__ import annotations

import dataclasses
import time
import zipfile
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distances import DISTANCES
from repro.core.posterior import Posterior
from repro.core.priors import UniformBoxPrior, schedule_prior
from repro.core.summaries import (
    SummarySpec,
    apply_summary,
    get_distance_kind,
    get_summary,
    lower_summary,
    pool_channels,
    pool_factor,
    summary_distance,
)
from repro.epi import engine
from repro.epi.data import CountryData
from repro.epi.models import get_model
from repro.epi.spec import InterventionSchedule
from repro.ioutils import atomic_write

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ABCConfig:
    """Configuration of a parallel ABC inference run."""

    batch_size: int = 100_000  # simulations per run (global)
    tolerance: float = 2e5
    target_accepted: int = 100
    strategy: str = "outfeed"  # "outfeed" | "topk"
    chunk_size: int = 10_000  # outfeed chunk granularity (paper default)
    top_k: int = 5  # samples returned per run under "topk"
    max_runs: int = 100_000
    distance: str = "euclidean"
    backend: str = "xla_fused"
    num_days: int = 49
    #: registry name of the compartmental model to infer (repro.epi.models)
    model: str = "siard"
    #: wave-loop driver: "host" (per-wave host sync, the original structure),
    #: "device" (one jitted lax.while_loop over waves with donated accept
    #: buffers), or "auto" (device for "outfeed" when the buffer fits, else
    #: host). The device loop yields the same same-seed accepted set as the
    #: host outfeed path (pinned by tests/test_wave_loop.py).
    wave_loop: str = "auto"
    #: optional piecewise-constant intervention schedule (repro.epi.spec):
    #: theta widens with per-window scale columns and the simulators apply
    #: the day-effective parameters; None keeps the constant-theta path
    #: bit-identical to previous releases
    schedule: Optional[InterventionSchedule] = None
    #: Pallas dispatch: True forces the interpreter (CPU correctness mode),
    #: False forces a compiled kernel, None auto-selects by backend
    #: (interpret only when jax runs on CPU)
    interpret: Optional[bool] = None
    #: summary statistic compared by `distance`: a registry name
    #: (core.summaries.SUMMARIES), a SummarySpec, or None for the paper's raw
    #: daily trajectories. Every backend lowers every (summary, distance)
    #: pair; the default (None, "euclidean") is bit-identical to pre-summary
    #: releases on all three backends (pinned by tests/test_summaries.py).
    summary: Optional[object] = None
    #: Pallas kernel tile (samples per grid cell). None auto-resolves via
    #: kernels.ops.resolve_tile (legacy 1024-lane default) or, with
    #: `autotune`, to the cached measured winner. An explicit tile must be a
    #: multiple of 128 dividing batch_size (validated loudly). Pure
    #: scheduling: accepted sets are bit-identical across tiles.
    tile: Optional[int] = None
    #: unroll factor of the xla_fused day scan (lax.scan unroll); None means
    #: 1 unless autotuning resolves a cached winner. Also pure scheduling.
    scan_unroll: Optional[int] = None
    #: consult (and on a miss, populate) the measured tuning cache under
    #: experiments/tuning/ at simulator-build time (repro.core.tuning);
    #: explicitly set tile/scan_unroll values always win over the cache
    autotune: bool = False
    #: metapop models only: a row-stochastic [R, R] mobility matrix (nested
    #: tuples) overriding the spec's static one — validated loudly here (rows
    #: must sum to 1); None keeps the model's own matrix. The matrix is a
    #: RUNTIME value on every backend (fconst lanes on pallas), so mobility
    #: sweeps share one compilation.
    mobility: Optional[Tuple[Tuple[float, ...], ...]] = None
    #: backend="npe" only: training hyperparameters (core.npe.NPEConfig);
    #: None uses the NPEConfig defaults. Ignored by the wave backends.
    npe: Optional[object] = None

    def __post_init__(self):
        if self.strategy not in ("outfeed", "topk"):
            raise ValueError(f"unknown strategy {self.strategy!r}")
        if self.strategy == "outfeed" and self.batch_size % self.chunk_size:
            raise ValueError("batch_size must be a multiple of chunk_size")
        if self.backend not in ("xla", "xla_fused", "pallas", "npe"):
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.npe is not None:
            from repro.core.npe import resolve_npe_config

            resolve_npe_config(self.npe)  # raises loudly on wrong type
            if self.backend != "npe":
                raise ValueError(
                    f"cfg.npe is set but backend={self.backend!r}; NPE "
                    "hyperparameters only apply to backend='npe'"
                )
        get_distance_kind(self.distance)  # raises on unknown names
        get_summary(self.summary)
        if self.wave_loop not in ("auto", "host", "device"):
            raise ValueError(f"unknown wave_loop {self.wave_loop!r}")
        if self.tile is not None:
            from repro.kernels.ops import resolve_tile

            # validates multiple-of-128 and batch divisibility, loudly
            resolve_tile(self.batch_size, self.tile)
        if self.scan_unroll is not None and self.scan_unroll < 1:
            raise ValueError(f"scan_unroll must be >= 1, got {self.scan_unroll}")
        if self.mobility is not None:
            from repro.epi.spec import validate_mobility

            # normalizes to nested float tuples (keeps the frozen config
            # hashable) and raises loudly on non-row-stochastic rows; the
            # region count must match the model's (checked at simulator
            # build, where the spec is resolved)
            object.__setattr__(
                self, "mobility",
                validate_mobility(self.mobility, len(self.mobility)),
            )
        if self.wave_loop == "device" and self.strategy == "topk":
            # the device loop compacts EVERY sub-tolerance sample (outfeed
            # harvest semantics); it has no per-wave k cap, so pairing it
            # with topk would silently change the accepted set
            raise ValueError(
                "wave_loop='device' implements outfeed harvest semantics; "
                "use strategy='outfeed' (or wave_loop='host' to keep the "
                "top-k truncation caveat)"
            )

    @property
    def num_chunks(self) -> int:
        return self.batch_size // self.chunk_size

    @property
    def summary_spec(self) -> SummarySpec:
        """The resolved SummarySpec (None -> identity)."""
        return get_summary(self.summary)


class RunOutput(NamedTuple):
    """Fixed-shape per-run device outputs (XLA requirement, paper §3.2)."""

    theta: Array  # outfeed: [n_chunks, chunk, p]; topk: [k, p]
    dist: Array  # outfeed: [n_chunks, chunk];    topk: [k]
    chunk_flags: Array  # outfeed: [n_chunks] bool;      topk: [0]
    accept_count: Array  # [] int32 — global accepted this run


def run_param_names(cfg: ABCConfig, spec) -> Tuple[str, ...]:
    """Posterior column names: the model's params plus any window scales."""
    if cfg.schedule is not None and not cfg.schedule.is_empty:
        return cfg.schedule.param_names(spec)
    return spec.param_names


SimulatorFn = Callable[[Array, Array], Array]  # (theta [B,p], key) -> dist [B]


def resolved_mobility(cfg: ABCConfig, spec) -> Optional[Array]:
    """cfg.mobility as an [R, R] f32 array, checked against the spec's
    region count; None defers to the spec's own (validated) matrix."""
    if cfg.mobility is None:
        return None
    if not spec.is_regional:
        raise ValueError(
            f"cfg.mobility set but model {spec.name!r} has no region axis"
        )
    if len(cfg.mobility) != spec.n_regions:
        raise ValueError(
            f"cfg.mobility is {len(cfg.mobility)}x{len(cfg.mobility)} but "
            f"model {spec.name!r} has {spec.n_regions} regions"
        )
    return jnp.asarray(cfg.mobility, jnp.float32)


class ScenarioData(NamedTuple):
    """Traced per-scenario data threaded through a parametric simulator.

    Everything here is a runtime value, never a compile constant: the wave
    loop compiled for one scenario shape serves every (dataset, intervention
    timing, scale bounds, tolerance) combination of that shape. The
    intervention fields make lockdown-day x scale campaign grids share one
    compilation: breakpoint days are an i32 vector, and the (possibly
    pinned) per-window scale bounds ride in the prior box arrays.
    """

    observed: Array  # [n_obs, T] f32
    population: Array  # f32 scalar
    a0: Array  # f32 scalar
    r0: Array  # f32 scalar
    d0: Array  # f32 scalar
    breakpoints: Array  # [n_windows] i32 (length 0 without a schedule)
    prior_lows: Array  # [p_total] f32 — the (widened) sampling box
    prior_highs: Array  # [p_total] f32


def make_parametric_simulator(spec, cfg: ABCConfig):
    """theta -> distance with the *dataset as traced arguments*.

    Returns `sim(theta [B,p], key, data: ScenarioData) -> dist [B]`. Because
    the observed series, the (population, a0, r0, d0) scalars and any
    intervention breakpoint days are inputs rather than baked-in constants,
    one jitted computation serves every dataset/scenario of the same
    (model, num_days, batch, schedule-shape) — the campaign runner relies on
    this to compile once per shape and sweep countries/seeds/interventions.

    The "pallas" backend bakes its scalars as static kernel constants and
    therefore cannot be parameterized this way (use `make_simulator`).
    """
    from repro.epi.spec import EpiModelConfig

    if cfg.backend == "npe":
        raise ValueError(
            "backend='npe' has no theta -> distance simulator; it is an "
            "amortized estimator — use repro.core.npe.train_npe / run_npe"
        )
    if cfg.backend == "pallas":
        raise ValueError(
            "pallas bakes (population, a0, r0, d0) into the kernel as static "
            "constants; build a per-dataset simulator with make_simulator"
        )
    schedule = cfg.schedule
    summary = cfg.summary_spec
    mob = resolved_mobility(cfg, spec)
    pool = pool_factor(summary, spec.n_regions)
    # identity summaries keep the legacy full-trajectory distance functions
    # (bit-compat for all three registered distances); a real summary lowers
    # as a post-hoc transform on the paper-faithful path
    dist_fn = DISTANCES[cfg.distance] if summary.is_identity else None

    def simulator(theta: Array, key: Array, data: ScenarioData) -> Array:
        observed, population, a0, r0, d0 = data[:5]
        breakpoints = data.breakpoints if isinstance(data, ScenarioData) else None
        mcfg = EpiModelConfig(
            population=population, num_days=cfg.num_days, a0=a0, r0=r0, d0=d0
        )
        if cfg.backend == "xla":
            sim = engine.simulate_observed(
                spec, theta, key, mcfg, schedule, breakpoints, mobility=mob
            )
            if dist_fn is not None:
                return dist_fn(sim, observed)
            lowered = lower_summary(
                summary, cfg.distance, observed, n_regions=spec.n_regions
            )
            return summary_distance(
                cfg.distance, lowered,
                apply_summary(summary, pool_channels(sim, pool, axis=-2)),
            )
        d, _ = engine.simulate_observed_lowmem(
            spec, theta, key, mcfg, observed, schedule, breakpoints,
            summary=summary, distance=cfg.distance,
            unroll=cfg.scan_unroll or 1, mobility=mob,
        )
        return d

    return simulator


def scenario_data(
    dataset: CountryData, cfg: ABCConfig, prior: Optional[UniformBoxPrior] = None
) -> ScenarioData:
    """Pack a dataset into the traced-argument tuple of a parametric simulator."""
    prior = prior or schedule_prior(get_model(cfg.model), cfg.schedule)
    breakpoints = (
        cfg.schedule.breakpoints if cfg.schedule is not None else ()
    )
    return ScenarioData(
        observed=jnp.asarray(dataset.observed[:, : cfg.num_days], jnp.float32),
        population=jnp.float32(dataset.population),
        a0=jnp.float32(dataset.a0),
        r0=jnp.float32(dataset.r0),
        d0=jnp.float32(dataset.d0),
        breakpoints=jnp.asarray(breakpoints, jnp.int32),
        prior_lows=jnp.asarray(prior.lows, jnp.float32),
        prior_highs=jnp.asarray(prior.highs, jnp.float32),
    )


def make_simulator(dataset: CountryData, cfg: ABCConfig) -> SimulatorFn:
    """Build the batched theta -> distance function for the chosen backend.

    The model spec comes from `cfg.model`; the dataset must hold series for
    the same observed channels (checked here, not at run time). With
    `cfg.schedule`, theta must carry the widened scale columns
    (`schedule_prior(spec, cfg.schedule)` samples the right layout).
    """
    if cfg.backend == "npe":
        raise ValueError(
            "backend='npe' has no theta -> distance simulator; it is an "
            "amortized estimator — use repro.core.npe.train_npe / run_npe"
        )
    if cfg.autotune:
        # fill tile / scan_unroll from the measured tuning cache (a miss
        # runs the search once and persists it); returns autotune=False so
        # the tuner's own measurement probes land in this branch's else
        from repro.core import tuning

        cfg = tuning.resolve_tuned(dataset, cfg)
    spec = get_model(cfg.model)
    if not dataset.compatible_with(spec):
        raise ValueError(
            f"dataset {dataset.name!r} holds {dataset.model!r} series; model "
            f"{spec.name!r} observes different channels"
        )
    mcfg = dataset.model_config(cfg.num_days)
    observed = jnp.asarray(dataset.observed[:, : cfg.num_days], jnp.float32)

    if cfg.backend in ("xla", "xla_fused"):
        parametric = make_parametric_simulator(spec, cfg)
        data = scenario_data(dataset, cfg)

        def simulator(theta: Array, key: Array) -> Array:
            return parametric(theta, key, data)

    else:  # pallas
        from repro.kernels import ops as kernel_ops

        mob = resolved_mobility(cfg, spec)

        def simulator(theta: Array, key: Array) -> Array:
            # The kernel uses a counter-based hash RNG; derive a 32-bit seed
            # from the threefry key so runs stay deterministic & resumable.
            seed = jax.random.key_data(key).ravel()[-1].astype(jnp.uint32)
            return kernel_ops.abc_sim_distance(
                theta,
                seed,
                observed,
                population=mcfg.population,
                a0=mcfg.a0,
                r0=mcfg.r0,
                d0=mcfg.d0,
                model=spec,
                schedule=cfg.schedule,
                tile=cfg.tile,
                interpret=cfg.interpret,
                summary=cfg.summary_spec,
                distance=cfg.distance,
                mobility=mob,
            )

    return simulator


def abc_run_batch(
    prior: UniformBoxPrior, simulator: SimulatorFn, cfg: ABCConfig
) -> Callable[[Array], RunOutput]:
    """Build the device-side computation for ONE run (one batch).

    Returned callable takes the per-run PRNG key. Pure & jittable; sharding is
    applied by the caller (see core.distributed / launch.abc_run).
    """
    p = prior.dim

    def run(key: Array) -> RunOutput:
        k_prior, k_sim = jax.random.split(key)
        theta = prior.sample(k_prior, (cfg.batch_size,))  # [B, p]
        dist = simulator(theta, k_sim)  # [B]
        # Failed/NaN simulations never count as accepted.
        dist = jnp.where(jnp.isnan(dist), jnp.inf, dist)
        accept = dist <= cfg.tolerance
        count = jnp.sum(accept.astype(jnp.int32))

        if cfg.strategy == "outfeed":
            nc, cs = cfg.num_chunks, cfg.chunk_size
            theta_c = theta.reshape(nc, cs, p)
            dist_c = dist.reshape(nc, cs)
            flags = jnp.any(accept.reshape(nc, cs), axis=1)
            return RunOutput(theta_c, dist_c, flags, count)

        # top-k: k smallest distances (paper's GPU strategy)
        neg_top, idx = jax.lax.top_k(-dist, cfg.top_k)
        return RunOutput(
            theta[idx], -neg_top, jnp.zeros((0,), bool), count
        )

    return run


# --------------------------------------------------------------------------
# Device-resident wave loop
# --------------------------------------------------------------------------

class WaveLoopOutput(NamedTuple):
    """Outputs of one device-resident wave-loop invocation.

    The accept buffers are laid out as `shards` contiguous segments of
    `capacity` rows each; segment i holds `fill_counts[i]` valid rows. This
    layout is a cross-runner CONTRACT: the sharded runners
    (core.distributed) emit one segment per device, the lockstep reference
    (core.scaling.make_reference_wave_runner) emits the same segments on a
    single device, and tests/test_scaling.py pins the two bit-identical —
    so harvest/checkpoint code never cares which topology produced a buffer.
    """

    theta_buf: Array  # [shards * capacity, p]
    dist_buf: Array  # [shards * capacity]
    n_accepted: Array  # [] int32 — TOTAL accepted (may exceed buffer fill)
    waves_done: Array  # [] int32 — waves executed by THIS invocation
    fill_counts: Array  # [shards] int32 — valid rows per buffer segment


#: auto mode only picks the device loop when the accept buffer stays small
#: enough to live comfortably on one device (rows, not bytes)
_AUTO_DEVICE_MAX_ROWS = 4_000_000


def wave_capacity(cfg: ABCConfig, batch_size: Optional[int] = None) -> int:
    """Accept-buffer rows per shard: never overflows within one wave.

    The loop only enters a wave while accepted < target, and a wave adds at
    most one batch, so `target + batch - 1` bounds the fill — the final
    wave's overshoot is retained exactly like the host outfeed path.
    """
    return cfg.target_accepted + (batch_size or cfg.batch_size)


def compact_accepted(th_buf, d_buf, fill, theta, dist, accept, capacity: int):
    """Scatter accepted rows into the buffer's next free slots.

    Fixed shapes throughout: rejected rows get an out-of-bounds slot and are
    dropped by the scatter. Returns (th_buf, d_buf, new_fill). Shared by the
    ABC wave loop and the SMC device round — the capacity-edge semantics
    exist exactly once.
    """
    slot = fill + jnp.cumsum(accept.astype(jnp.int32)) - 1
    slot = jnp.where(accept, slot, capacity)
    th_buf = th_buf.at[slot].set(theta, mode="drop")
    d_buf = d_buf.at[slot].set(dist, mode="drop")
    return th_buf, d_buf, fill + jnp.sum(accept, dtype=jnp.int32)


def wave_loop_body(
    prior: UniformBoxPrior,
    sim_call,  # (theta, key, data) -> dist
    batch_size: int,
    capacity: int,
    *,
    fold_axis=None,  # device index to fold into the run key (shard_map path)
    count_all=None,  # per-shard fill -> global total (psum under shard_map)
):
    """One wave: sample -> simulate -> compare -> compact into the buffer.

    Returns a `body(carry)` for `lax.while_loop` with carry
    `(wave, n_global, fill, theta_buf, dist_buf)`; the extra run inputs
    (key, run_idx0, tolerance, data, and `n_base` under `count_all`) are
    closed over by the caller via `functools.partial`-style nesting in
    `build_wave_loop`.

    Under `count_all` the global count is re-derived every wave as
    `n_base + count_all(fill)`, never accumulated from per-wave increments:
    XLA's TPU compiler moves an all-reduce whose result only feeds a
    loop-carried sum out of the loop without looking at the loop condition,
    so the stop test would read each shard's local count.
    """

    def body(carry, key, run_idx0, tolerance, data, n_base=None):
        w, n_global, fill, th_buf, d_buf = carry
        k = jax.random.fold_in(key, run_idx0 + w)
        if fold_axis is not None:
            k = jax.random.fold_in(k, fold_axis())
        k_prior, k_sim = jax.random.split(k)
        if isinstance(data, ScenarioData):
            # sample inside the scenario's traced box (bit-identical math to
            # the baked path) so one compiled loop serves every scenario of
            # this shape, including swept intervention-scale bounds
            theta = prior.sample(k_prior, (batch_size,),
                                 data.prior_lows, data.prior_highs)
        else:
            theta = prior.sample(k_prior, (batch_size,))
        dist = sim_call(theta, k_sim, data)
        dist = jnp.where(jnp.isnan(dist), jnp.inf, dist)
        accept = dist <= tolerance
        th_buf, d_buf, new_fill = compact_accepted(
            th_buf, d_buf, fill, theta, dist, accept, capacity
        )
        if count_all is None:
            n_global = n_global + (new_fill - fill)
        else:
            n_global = n_base + count_all(new_fill)
        return (w + 1, n_global, new_fill, th_buf, d_buf)

    return body


def build_wave_loop(
    prior: UniformBoxPrior,
    sim_call,  # (theta, key, data) -> dist
    cfg: ABCConfig,
    *,
    batch_size: Optional[int] = None,
    capacity: Optional[int] = None,
    fold_axis=None,
    count_all=None,
    shard_hint=None,  # optional fn applied to per-wave batch arrays (pjit path)
):
    """Build the un-jitted device-resident wave loop.

    loop(key, run_idx0, theta_buf, dist_buf, n0, fill0, max_waves,
         tolerance, data) -> WaveLoopOutput

    A single `lax.while_loop` runs waves until the GLOBAL accepted count
    reaches `cfg.target_accepted` or `max_waves` waves have run. Sample
    streams are identical to the host loop: wave w uses
    `fold_in(key, run_idx0 + w)` (plus a device fold under shard_map),
    exactly as `run_abc`/`make_shardmap_runner` key their runs.
    """
    B = batch_size or cfg.batch_size
    cap = capacity or wave_capacity(cfg, B)
    target = cfg.target_accepted
    inner = sim_call
    if shard_hint is not None:
        def inner(theta, key, data):  # noqa: F811 — sharded wrapper
            return shard_hint(sim_call(shard_hint(theta), key, data))
    body_fn = wave_loop_body(
        prior, inner, B, cap, fold_axis=fold_axis, count_all=count_all
    )

    def loop(key, run_idx0, theta_buf, dist_buf, n0, fill0, max_waves,
             tolerance, data):
        run_idx0 = jnp.asarray(run_idx0, jnp.int32)
        max_waves = jnp.asarray(max_waves, jnp.int32)
        n0 = jnp.asarray(n0, jnp.int32)
        fill0 = jnp.asarray(fill0, jnp.int32)
        # accepts held before this call that no shard's fill counts
        n_base = None if count_all is None else n0 - count_all(fill0)

        def cond(carry):
            w, n_global, *_ = carry
            return jnp.logical_and(n_global < target, w < max_waves)

        def body(carry):
            return body_fn(carry, key, run_idx0, tolerance, data, n_base)

        w, n, fill, th_buf, d_buf = jax.lax.while_loop(
            cond, body, (jnp.int32(0), n0, fill0, theta_buf, dist_buf)
        )
        return WaveLoopOutput(
            th_buf, d_buf, n, w, jnp.minimum(fill, cap)[None]
        )

    return loop


@dataclasses.dataclass
class WaveRunner:
    """A compiled device-resident wave loop plus its buffer layout.

    `fn(key, run_idx0, theta_buf, dist_buf, n0, fill0, max_waves, tolerance,
    data)` is jitted with the buffers donated; `data` is the traced
    per-scenario tuple (or None when the simulator baked the dataset in).
    `shards` > 1 means the buffers are laid out as per-device segments
    (distributed runners).
    """

    fn: Callable[..., WaveLoopOutput]
    capacity: int  # rows per shard segment
    shards: int
    n_params: int
    cfg: ABCConfig
    data: Optional[ScenarioData] = None

    def init(self, state: "ABCState"):
        """Device buffers seeded from (possibly resumed) host state.

        Returns the carry (theta_buf, dist_buf, n0, fill0). Existing accepted
        samples are split evenly across shard segments (exact order is
        preserved for shards == 1, the pinned single-device case).
        """
        theta, dist = state.to_arrays()
        n = theta.shape[0]
        th_buf = np.zeros((self.shards * self.capacity, self.n_params), np.float32)
        d_buf = np.full((self.shards * self.capacity,), np.inf, np.float32)
        fills = np.zeros((self.shards,), np.int32)
        splits = np.array_split(np.arange(n), self.shards)
        for s, idx in enumerate(splits):
            if idx.size > self.capacity:
                raise ValueError(
                    f"resumed state ({n} accepted) overflows the wave buffer "
                    f"({self.shards} x {self.capacity}); raise target/batch"
                )
            lo = s * self.capacity
            th_buf[lo : lo + idx.size] = theta[idx]
            d_buf[lo : lo + idx.size] = dist[idx]
            fills[s] = idx.size
        fill0 = fills if self.shards > 1 else np.int32(fills[0])
        return (jnp.asarray(th_buf), jnp.asarray(d_buf), np.int32(n), fill0)

    def __call__(self, key, run_idx0: int, carry, max_waves: int) -> WaveLoopOutput:
        th_buf, d_buf, n0, fill0 = carry
        return self.fn(
            key, np.int32(run_idx0), th_buf, d_buf, n0, fill0,
            np.int32(max_waves), np.float32(self.cfg.tolerance), self.data,
        )

    def carry_of(self, out: WaveLoopOutput):
        fill = out.fill_counts if self.shards > 1 else out.fill_counts[0]
        return (out.theta_buf, out.dist_buf, out.n_accepted, fill)

    def harvest(self, out: WaveLoopOutput, state: "ABCState") -> None:
        """Replace the state's accepted set with the buffers' contents.

        Unlike the host loop's incremental appends, the buffers are
        cumulative — they carry every accepted sample so far (including any
        resumed prefix), so this *replaces* rather than extends.
        """
        th = np.asarray(out.theta_buf)
        d = np.asarray(out.dist_buf)
        fills = np.asarray(out.fill_counts)
        state.accepted_theta = []
        state.accepted_dist = []
        for s, c in enumerate(fills):
            c = int(c)
            if c:
                lo = s * self.capacity
                state.accepted_theta.append(th[lo : lo + c])
                state.accepted_dist.append(d[lo : lo + c])


def make_wave_runner(
    prior: UniformBoxPrior, simulator: SimulatorFn, cfg: ABCConfig
) -> WaveRunner:
    """Single-device wave runner over a dataset-baked simulator."""
    loop = build_wave_loop(prior, lambda th, k, _data: simulator(th, k), cfg)
    fn = jax.jit(loop, donate_argnums=(2, 3))
    return WaveRunner(
        fn=fn, capacity=wave_capacity(cfg), shards=1, n_params=prior.dim, cfg=cfg
    )


def _auto_device_loop(cfg: ABCConfig) -> bool:
    """auto: device loop for outfeed runs whose accept buffer stays small."""
    if cfg.wave_loop == "device":
        return True
    if cfg.wave_loop == "host":
        return False
    return (
        cfg.strategy == "outfeed"
        and wave_capacity(cfg) <= _AUTO_DEVICE_MAX_ROWS
    )


@dataclasses.dataclass
class ABCState:
    """Resumable sampler state — the fault-tolerance unit for inference.

    Work is addressed by (base seed, run index): any worker can recompute any
    run, so restart/elastic-rescale only needs this state (DESIGN.md §3).
    """

    run_idx: int = 0
    simulations: int = 0
    accepted_theta: list = dataclasses.field(default_factory=list)
    accepted_dist: list = dataclasses.field(default_factory=list)
    #: parameter dimension, set from the model/prior by run_abc (or on load);
    #: required only to give the empty-case arrays a concrete shape
    n_params: Optional[int] = None

    @property
    def n_accepted(self) -> int:
        return sum(int(t.shape[0]) for t in self.accepted_theta)

    def to_arrays(self):
        if not self.accepted_theta:
            # shape derives from the model/prior — NOT a hardcoded paper dim
            return (
                np.zeros((0, self.n_params or 0), np.float32),
                np.zeros((0,), np.float32),
            )
        return (
            np.concatenate(self.accepted_theta, axis=0),
            np.concatenate(self.accepted_dist, axis=0),
        )

    def save(self, path: str) -> None:
        """Atomic save via the shared `repro.ioutils.atomic_write` helper:
        an interrupted save (crash, preemption mid-campaign) can never leave
        a truncated checkpoint at `path` — the previous complete file, if
        any, survives."""
        th, d = self.to_arrays()
        with atomic_write(path, "wb") as f:
            np.savez(
                f, run_idx=self.run_idx, simulations=self.simulations,
                theta=th, dist=d,
            )

    _REQUIRED_KEYS = ("run_idx", "simulations", "theta", "dist")

    @staticmethod
    def load(path: str) -> "ABCState":
        """Load a checkpoint, rejecting corrupt/partial files loudly.

        A truncated or otherwise unreadable file raises ValueError with a
        clear remediation message instead of surfacing a bare zipfile/KeyError
        deep inside a resumed campaign. A missing file is NOT corruption —
        FileNotFoundError propagates untouched."""
        try:
            z = np.load(path, allow_pickle=False)
            missing = [k for k in ABCState._REQUIRED_KEYS if k not in z.files]
            if missing:
                raise ValueError(f"missing arrays {missing}")
            theta = np.asarray(z["theta"], np.float32)
            dist = np.asarray(z["dist"], np.float32)
            if theta.ndim != 2 or dist.shape != (theta.shape[0],):
                raise ValueError(
                    f"inconsistent shapes theta={theta.shape} dist={dist.shape}"
                )
            st = ABCState(
                run_idx=int(z["run_idx"]),
                simulations=int(z["simulations"]),
                n_params=int(theta.shape[1]),
            )
        except FileNotFoundError:
            raise
        except (zipfile.BadZipFile, OSError, KeyError, ValueError) as e:
            raise ValueError(
                f"corrupt or incomplete ABC checkpoint {path!r} ({e}); it was "
                "probably truncated by an interrupted save — delete it to "
                "restart this scenario from scratch"
            ) from e
        if theta.shape[0]:
            st.accepted_theta = [theta]
            st.accepted_dist = [dist]
        return st


def _harvest(out: RunOutput, cfg: ABCConfig, state: ABCState) -> int:
    """Host-side postprocessing of one run's outputs (paper §3.2 / Table 4).

    Pulls to host ONLY what the strategy marked for transfer, filters
    dist <= eps, and appends accepted samples to the state. Returns the
    number of accepted samples harvested.
    """
    n_new = 0
    if cfg.strategy == "outfeed":
        flags = np.asarray(out.chunk_flags)  # [n_chunks] — tiny transfer
        for ci in np.nonzero(flags)[0]:
            # per-chunk D2H transfer, mirroring the IPU outfeed
            d = np.asarray(out.dist[ci])
            th = np.asarray(out.theta[ci])
            m = d <= cfg.tolerance
            if m.any():
                state.accepted_theta.append(th[m])
                state.accepted_dist.append(d[m])
                n_new += int(m.sum())
    else:  # topk
        d = np.asarray(out.dist)
        th = np.asarray(out.theta)
        m = d <= cfg.tolerance
        if m.any():
            state.accepted_theta.append(th[m])
            state.accepted_dist.append(d[m])
            n_new += int(m.sum())
        # NOTE: if accept_count > k the paper accepts losing samples (their
        # Top-k caveat); we surface the same behaviour.
    return n_new


def run_abc(
    dataset: CountryData,
    cfg: ABCConfig,
    key: Array | int = 0,
    prior: Optional[UniformBoxPrior] = None,
    state: Optional[ABCState] = None,
    run_fn: Optional[Callable[[Array], RunOutput]] = None,
    wave_runner: Optional[WaveRunner] = None,
    checkpoint_every: int = 0,
    checkpoint_path: Optional[str] = None,
    verbose: bool = False,
) -> Posterior:
    """Host driver: iterate runs until `target_accepted` posterior samples.

    Two drivers share the stream semantics (wave i == fold_in(key, i)):

      * host loop  — `run_fn` (a jitted `abc_run_batch`, possibly pre-sharded
        for multi-device) is invoked once per wave and harvested on the host.
      * device loop — `wave_runner` keeps the whole accept/reject loop in one
        jitted lax.while_loop with donated accept buffers; the host is only
        re-entered when the target is met, the budget is exhausted, or a
        checkpoint is due. Selected by `cfg.wave_loop` ("auto" picks it for
        outfeed-strategy runs) or by passing `wave_runner` explicitly
        (see core.distributed.make_wave_runner for the sharded styles).
    """
    if cfg.backend == "npe":
        # the amortized backend has no wave loop: train the estimator, then
        # one forward pass. The wave-driver knobs make no sense here.
        if run_fn is not None or wave_runner is not None or state is not None:
            raise ValueError(
                "backend='npe' does not run waves; run_fn / wave_runner / "
                "resumable state do not apply"
            )
        from repro.core import npe

        return npe.run_npe(dataset, cfg, key, prior=prior, verbose=verbose)
    spec = get_model(cfg.model)
    if isinstance(key, int):
        key = jax.random.PRNGKey(key)
    prior = prior or schedule_prior(spec, cfg.schedule)
    state = state or ABCState()
    if state.n_params is None:
        state.n_params = prior.dim
    elif state.n_params != prior.dim:
        raise ValueError(
            f"resumed state holds {state.n_params}-parameter samples but model "
            f"{spec.name!r} has {prior.dim} parameters — wrong checkpoint?"
        )
    if run_fn is not None and wave_runner is None and cfg.wave_loop == "device":
        raise ValueError(
            "cfg.wave_loop='device' conflicts with an explicit host-loop "
            "run_fn; pass a wave_runner (see distributed.make_wave_runner) "
            "or drop one of the two"
        )
    if wave_runner is None and run_fn is None and _auto_device_loop(cfg):
        wave_runner = make_wave_runner(prior, make_simulator(dataset, cfg), cfg)
    if wave_runner is not None:
        return _run_abc_device(
            cfg, key, state, wave_runner, spec,
            checkpoint_every=checkpoint_every, checkpoint_path=checkpoint_path,
            verbose=verbose,
        )
    if run_fn is None:
        simulator = make_simulator(dataset, cfg)
        run_fn = jax.jit(abc_run_batch(prior, simulator, cfg))

    t0 = time.time()
    postproc_s = 0.0
    while state.n_accepted < cfg.target_accepted and state.run_idx < cfg.max_runs:
        run_key = jax.random.fold_in(key, state.run_idx)
        out = run_fn(run_key)
        out = jax.tree.map(jax.block_until_ready, out)
        tp = time.time()
        _harvest(out, cfg, state)
        postproc_s += time.time() - tp
        state.run_idx += 1
        state.simulations += cfg.batch_size
        if verbose and state.run_idx % 50 == 0:
            print(
                f"[abc] run {state.run_idx}: accepted {state.n_accepted}/"
                f"{cfg.target_accepted}"
            )
        if (
            checkpoint_every
            and checkpoint_path
            and state.run_idx % checkpoint_every == 0
        ):
            state.save(checkpoint_path)

    theta, dist = state.to_arrays()
    # every harvested sample is returned (a run may overshoot target_accepted;
    # the paper keeps the overshoot too — callers can slice with Posterior.top)
    post = Posterior(
        theta=theta,
        distances=dist,
        tolerance=cfg.tolerance,
        param_names=run_param_names(cfg, spec),
        runs=state.run_idx,
        simulations=state.simulations,
        wall_time_s=time.time() - t0,
    )
    post.postproc_time_s = postproc_s  # type: ignore[attr-defined]
    return post


def _run_abc_device(
    cfg: ABCConfig,
    key: Array,
    state: ABCState,
    wave_runner: WaveRunner,
    spec,
    checkpoint_every: int = 0,
    checkpoint_path: Optional[str] = None,
    verbose: bool = False,
) -> Posterior:
    """Device-loop driver: segments of waves between host syncs.

    Without checkpointing there is exactly ONE device invocation — the
    while_loop runs until the target is met or `max_runs` is exhausted, and
    the buffers come back once. With checkpointing, each segment is bounded
    by `checkpoint_every` waves so a crash loses at most one segment.
    """
    t0 = time.time()
    postproc_s = 0.0
    carry = wave_runner.init(state)
    while state.n_accepted < cfg.target_accepted and state.run_idx < cfg.max_runs:
        seg = cfg.max_runs - state.run_idx
        if checkpoint_every and checkpoint_path:
            seg = min(seg, checkpoint_every)
        out = wave_runner(key, state.run_idx, carry, seg)
        waves = int(out.waves_done)  # the segment's single host sync
        tp = time.time()
        wave_runner.harvest(out, state)
        postproc_s += time.time() - tp
        carry = wave_runner.carry_of(out)
        state.run_idx += waves
        state.simulations += waves * cfg.batch_size
        if verbose:
            print(
                f"[abc] run {state.run_idx}: accepted {state.n_accepted}/"
                f"{cfg.target_accepted} (device wave loop)"
            )
        if checkpoint_every and checkpoint_path:
            state.save(checkpoint_path)
        if waves == 0:  # budget/target already consumed; avoid a spin
            break

    theta, dist = state.to_arrays()
    post = Posterior(
        theta=theta,
        distances=dist,
        tolerance=cfg.tolerance,
        param_names=run_param_names(cfg, spec),
        runs=state.run_idx,
        simulations=state.simulations,
        wall_time_s=time.time() - t0,
    )
    post.postproc_time_s = postproc_s  # type: ignore[attr-defined]
    return post


def calibrate_tolerance(
    dataset: CountryData,
    cfg: ABCConfig,
    key: Array | int = 0,
    quantile: float = 1e-3,
    n_pilot: int = 65_536,
    prior: Optional[UniformBoxPrior] = None,
) -> float:
    """Auto-pick a tolerance as a quantile of the pilot distance distribution.

    The paper tunes epsilon per country by hand ("the tolerance had to be
    adjusted on an individual basis", §5); this calibrates it from a pilot
    wave of prior-predictive simulations so the expected acceptance rate —
    and therefore total runtime — is controlled a priori:
        expected runs ~= target_accepted / (quantile * batch_size).
    """
    if isinstance(key, int):
        key = jax.random.PRNGKey(key)
    prior = prior or schedule_prior(get_model(cfg.model), cfg.schedule)
    simulator = jax.jit(make_simulator(dataset, cfg))
    per_wave = min(n_pilot, cfg.batch_size)
    dists = []
    for w in range(max(1, n_pilot // per_wave)):
        kw = jax.random.fold_in(key, w)
        theta = prior.sample(jax.random.fold_in(kw, 0), (per_wave,))
        d = np.asarray(simulator(theta, jax.random.fold_in(kw, 1)))
        dists.append(d[np.isfinite(d)])
    d = np.concatenate(dists)
    return float(np.quantile(d, quantile))
