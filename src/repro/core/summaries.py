"""Declarative summary statistics + weighted distances for ABC calibration.

The paper compares raw (A, R, D) trajectories with a plain Euclidean
distance; the SBI-assessment literature (PAPERS.md) shows the choice of
summary statistic and distance weighting dominates posterior quality for
stochastic epidemic models. This module makes both first-class calibration
components, expressed so that every simulation backend can lower them:

  * `SummarySpec` — a composable transform of the observed-channel series:
    optional cumulative channels, optional log1p, optional `bin_days`-day
    binning (weekly = 7), optional per-channel weights. Transforms compose in
    the order cumulative -> binning -> log1p (log of weekly totals).
  * `DISTANCE_KINDS` — the distance family over summary values: weighted L2
    ("euclidean"), weighted mean-L1 ("mae") and observed-scale-normalized L2
    ("normalized_euclidean"); the names deliberately mirror the legacy
    `repro.core.distances.DISTANCES` registry so `ABCConfig.distance` values
    are unchanged.

Every (summary, distance) pair reduces to ONE running-accumulator shape that
all three backends share (the generalization of the fused running squared
distance, DESIGN.md §2). Per day t, with per-channel carries `cum` and `bin`:

    cum  += x_t                        # running cumulative
    v     = cum  if cumulative else x_t
    bin   = v if cumulative else bin + v   # cumulative: END-OF-BIN level;
                                           # rates: running within-bin SUM
    flush = ((t+1) % bin_days == 0) or (t == T-1)   # partial final bin counts
    s     = log1p(max(bin, 0)) if log1p else bin
    acc  += flush * sum_c w_c * |s_c - obs_summary_c[t]| ** power
    bin  *= 1 - flush
    dist  = sqrt(acc) | acc / n_terms                # by distance kind

(Binning a cumulative channel takes the latest cumulative value — "weekly
cumulative deaths" means the level at the end of each week — rather than
summing levels within the bin, which would scale each term by its bin
length and silently down-weight a partial final bin.)

The observed side is precomputed once (`lower_summary`) in the SAME running
layout, so the comparison at flush days is exact and the values at non-flush
days are ignored. The identity spec with the "euclidean" kind degenerates to
exactly the legacy accumulation (flush == 1 and w == 1 every day; every
extra op is a multiply-by-1.0 or a constant-false select, both bit-exact),
which is how the default path stays bit-identical to pre-summary releases —
pinned by tests/test_summaries.py.

Lowerings (consumers):
  * `apply_summary` + `summary_distance` — vectorized post-hoc transform for
    the paper-faithful "xla" backend (full [B, n_obs, T] trajectories).
  * `running_day` / `running_finalize`  — per-day fold for the "xla_fused"
    scan (repro.epi.engine.simulate_observed_lowmem) and the kernel oracle
    (repro.kernels.ref).
  * the Pallas kernel (repro.kernels.abc_sim) re-expresses `running_day`
    with traced selects: the lowered weights/flags ride scalar const lanes
    like the intervention breakpoints, so a summary/distance sweep reuses
    one compiled kernel (pinned by a jit-cache test).

This module imports nothing from the rest of the repo, so every layer can
depend on it without cycles.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SummarySpec:
    """A composable summary transform of the observed-channel series.

    Applied identically to the simulated and the observed side; the
    transforms compose as cumulative -> `bin_days`-binning -> log1p.
    """

    name: str = "identity"
    #: per-channel cumulative sums over time (e.g. cumulative deaths)
    cumulative: bool = False
    #: log1p of the (clamped non-negative) values — tames heavy-tailed counts
    log1p: bool = False
    #: bin length in days; 1 = daily (no binning), 7 = weekly totals. The
    #: final bin may be partial (it flushes on the last day regardless).
    bin_days: int = 1
    #: optional per-channel weights (length n_observed); None = all 1.0.
    #: For metapop models, either the flattened total channel count or the
    #: per-region count (then tiled identically across regions).
    channel_weights: Optional[Tuple[float, ...]] = None
    #: metapop models only: sum each observed channel across regions BEFORE
    #: the transform chain, comparing national aggregates instead of
    #: per-region series (the region axis of the summary accumulator
    #: collapses; requires `n_regions` at lowering time). No-op at R=1.
    region_pool: bool = False

    def __post_init__(self):
        if self.bin_days < 1:
            raise ValueError(f"bin_days must be >= 1, got {self.bin_days}")
        if self.channel_weights is not None:
            object.__setattr__(
                self, "channel_weights",
                tuple(float(w) for w in self.channel_weights),
            )
            if any(w < 0 for w in self.channel_weights):
                raise ValueError("channel weights must be non-negative")

    @property
    def is_identity(self) -> bool:
        """True when the transform is a no-op (the paper's raw statistic)."""
        return (
            not self.cumulative
            and not self.log1p
            and self.bin_days == 1
            and self.channel_weights is None
            and not self.region_pool
        )

    def tag(self) -> str:
        """Compact filesystem-safe label for scenario/checkpoint names.

        The bare name is only trusted when this spec IS the registry entry
        of that name; any other spec gets a parameter-derived tag, so two
        different statistics can never share a scenario name (and therefore
        a campaign checkpoint directory)."""
        if SUMMARIES.get(self.name) == self:
            return self.name
        if self.is_identity:
            return "identity"
        parts = []
        if self.cumulative:
            parts.append("cum")
        if self.bin_days > 1:
            parts.append(f"bin{self.bin_days}")
        if self.log1p:
            parts.append("log1p")
        if self.channel_weights is not None:
            parts.append("w" + "-".join(f"{w:g}" for w in self.channel_weights))
        if self.region_pool:
            parts.append("rpool")
        return "_".join(parts)


#: registry of named summary statistics (ABCConfig.summary / --summary / the
#: campaign's --summaries axis accept these names or SummarySpec instances)
SUMMARIES = {
    "identity": SummarySpec(),
    "weekly": SummarySpec("weekly", bin_days=7),
    "cumulative": SummarySpec("cumulative", cumulative=True),
    "log_daily": SummarySpec("log_daily", log1p=True),
    "log_weekly": SummarySpec("log_weekly", bin_days=7, log1p=True),
    # metapop: per-channel national aggregates; identical to "identity" at R=1
    "region_pooled": SummarySpec("region_pooled", region_pool=True),
}


def list_summaries() -> Tuple[str, ...]:
    return tuple(sorted(SUMMARIES))


def get_summary(s) -> SummarySpec:
    """Resolve None (identity) / registry name / SummarySpec instance."""
    if s is None:
        return SUMMARIES["identity"]
    if isinstance(s, SummarySpec):
        return s
    if isinstance(s, str):
        try:
            return SUMMARIES[s]
        except KeyError:
            raise ValueError(
                f"unknown summary {s!r}; registered: {list_summaries()}"
            ) from None
    raise TypeError(f"summary must be None, a name or a SummarySpec; got {s!r}")


class DistanceKind(NamedTuple):
    """How the weighted per-term residuals reduce to one distance."""

    power: int  # 1 (absolute) | 2 (squared) residuals
    root: bool  # sqrt the accumulator at the end (L2 family)
    mean: bool  # divide by the number of summary terms (mean-L1 family)
    normalize: bool  # fold 1/observed-scale^2 into the channel weights


#: same keys as repro.core.distances.DISTANCES, so ABCConfig.distance values
#: carry over unchanged; here they act on SUMMARY values instead of raw days
DISTANCE_KINDS = {
    "euclidean": DistanceKind(power=2, root=True, mean=False, normalize=False),
    "mae": DistanceKind(power=1, root=False, mean=True, normalize=False),
    "normalized_euclidean": DistanceKind(
        power=2, root=True, mean=False, normalize=True
    ),
}


def get_distance_kind(name: str) -> DistanceKind:
    try:
        return DISTANCE_KINDS[name]
    except KeyError:
        raise ValueError(
            f"unknown distance {name!r}; registered: {tuple(sorted(DISTANCE_KINDS))}"
        ) from None


# indices into LoweredSummary.flags — the i32 selector vector the Pallas
# kernel reads off its const lanes (traced, so specs share one compile)
FLAG_CUMULATIVE, FLAG_LOG1P, FLAG_POWER, FLAG_ROOT, FLAG_BIN_DAYS = range(5)
N_FLAGS = 5


class LoweredSummary(NamedTuple):
    """Runtime (traced-compatible) values a backend needs for one
    (summary, distance) pair against one observed series."""

    obs_summary: Array  # [n_obs, T] — observed side in the running-bin layout
    flush: Array  # [T] f32 — 1.0 on days whose bin closes
    weights: Array  # [n_obs] f32 — channel weights incl. normalization
    mean_scale: Array  # [] f32 — 1/n_terms for mean-kind distances else 1.0
    flags: Array  # [N_FLAGS] i32 — selector vector (see FLAG_*)


def num_bins(num_days: int, bin_days: int) -> int:
    """Summary terms per channel (the final partial bin counts)."""
    return -(-num_days // bin_days)


def flush_mask(num_days: int, bin_days: int) -> Array:
    """[T] f32: 1.0 on the last day of each bin (incl. a partial final bin)."""
    t = np.arange(num_days)
    m = ((t + 1) % bin_days == 0) | (t == num_days - 1)
    return jnp.asarray(m, jnp.float32)


def pool_factor(spec: SummarySpec, n_regions: int) -> int:
    """Static region-pooling factor: `n_regions` when this spec pools the
    region axis of a metapop series, else 1 (identity). Backends branch on
    this at trace time, so R=1 and non-pooling paths stay bit-exact."""
    return n_regions if (spec.region_pool and n_regions > 1) else 1


def pool_channels(x: Array, pool: int, axis: int = -1) -> Array:
    """Sum a region-major flattened channel axis across regions.

    `axis` (-1 for per-day vectors [..., R*n], -2 for series [..., R*n, T])
    has length pool*n laid out region-major (channel r*n+c, matching
    `CompartmentalModel.total_observed_idx`); the result drops the region
    factor, length n. `pool <= 1` returns the input unchanged (bit-exact)."""
    if pool <= 1:
        return x
    axis = axis % x.ndim
    n_chan = x.shape[axis]
    if n_chan % pool:
        raise ValueError(
            f"cannot pool axis of length {n_chan} by region factor {pool}"
        )
    shape = x.shape[:axis] + (pool, n_chan // pool) + x.shape[axis + 1:]
    return jnp.sum(x.reshape(shape), axis=axis)


def apply_summary(spec: SummarySpec, series: Array) -> Array:
    """Vectorized summary transform, running-bin layout: [..., n_obs, T] ->
    [..., n_obs, T] where entry t holds the within-bin running value at day t
    (== the bin's summary value on flush days). Binning SUMS rate channels
    within each bin; a cumulative channel's bin value is its latest running
    level (module docstring), which for the cumulative series is just the
    series itself. With the identity spec the input is returned unchanged
    (bit-exact)."""
    x = jnp.asarray(series, jnp.float32)
    num_days = x.shape[-1]
    v = jnp.cumsum(x, axis=-1) if spec.cumulative else x
    if spec.bin_days > 1 and not spec.cumulative:
        cv = jnp.cumsum(v, axis=-1)
        t = np.arange(num_days)
        start = (t // spec.bin_days) * spec.bin_days  # first day of t's bin
        prev = jnp.where(
            jnp.asarray(start > 0), cv[..., np.maximum(start - 1, 0)], 0.0
        )
        v = cv - prev  # running within-bin sum at day t
    if spec.log1p:
        v = jnp.log1p(jnp.maximum(v, 0.0))
    return v


def lower_summary(
    spec: SummarySpec, distance: str, observed: Array, n_regions: int = 1
) -> LoweredSummary:
    """Precompute the observed-side summary + weights for one pair.

    `observed` [n_obs, T] may be a traced value (the campaign threads
    datasets through compiled wave loops as arguments); every output is then
    traced too. The flags vector is always concrete here — the Pallas path
    re-feeds it as a runtime argument so sweeps share one compiled kernel.

    For metapop models `observed` carries the region-major flattened channel
    axis ([R*n, T], `CompartmentalModel.total_observed_idx` order) and
    `n_regions` must be passed; a region-pooling spec then sums the observed
    side across regions here, so the lowered layout matches the pooled
    simulated series the backends feed to the accumulator. Per-region
    `channel_weights` (length n_obs / R) are tiled identically across
    regions.
    """
    kind = get_distance_kind(distance)
    obs = jnp.asarray(observed, jnp.float32)
    pool = pool_factor(spec, n_regions)
    obs = pool_channels(obs, pool, axis=-2)
    n_obs, num_days = obs.shape
    s = apply_summary(spec, obs)
    fl = flush_mask(num_days, spec.bin_days)
    nb = num_bins(num_days, spec.bin_days)
    if spec.channel_weights is not None:
        cw = spec.channel_weights
        if len(cw) != n_obs and n_regions > 1 and len(cw) * n_regions == n_obs:
            cw = cw * n_regions  # per-region weights, tiled region-major
        if len(cw) != n_obs:
            raise ValueError(
                f"summary {spec.tag()!r} has {len(spec.channel_weights)} channel "
                f"weights for {n_obs} observed channels"
            )
        w = jnp.asarray(cw, jnp.float32)
    else:
        w = jnp.ones((n_obs,), jnp.float32)
    if kind.normalize:
        # per-channel RMS of the observed summary over its flush days — the
        # cross-country comparability weighting (legacy normalized_euclidean
        # generalized to any summary); eps=1.0 matches the legacy distance
        msq = jnp.sum(fl * s * s, axis=-1) / nb
        scale = jnp.sqrt(msq) + 1.0
        w = w / (scale * scale)
    mean_scale = jnp.float32(1.0 / (n_obs * nb) if kind.mean else 1.0)
    flags = jnp.asarray(
        [int(spec.cumulative), int(spec.log1p), kind.power, int(kind.root),
         spec.bin_days],
        jnp.int32,
    )
    return LoweredSummary(s, fl, w, mean_scale, flags)


def summary_distance(
    distance: str, lowered: LoweredSummary, sim_summary: Array
) -> Array:
    """Post-hoc weighted distance over summary values: [..., n_obs, T] -> [...].

    The "xla" backend's lowering: `sim_summary` is `apply_summary` of the
    full simulated trajectories."""
    kind = get_distance_kind(distance)
    diff = sim_summary - lowered.obs_summary
    term = jnp.abs(diff) if kind.power == 1 else diff * diff
    acc = jnp.sum(lowered.flush * (lowered.weights[..., None] * term),
                  axis=(-2, -1))
    acc = acc * lowered.mean_scale
    return jnp.sqrt(acc) if kind.root else acc


def running_day(
    spec: SummarySpec,
    kind: DistanceKind,
    weights: Array,
    x: Array,  # [..., n_obs] — this day's observed-channel values
    obs_t: Array,  # [n_obs] (or broadcastable) — observed summary at day t
    flush_t: Array,  # [] f32 — 1.0 if day t closes a bin
    cum: Array,  # [..., n_obs] carry
    binv: Array,  # [..., n_obs] carry
    acc: Array,  # [...] carry
):
    """One day of the generalized running-distance accumulator (module
    docstring recurrence), tensor layout. Shared by the fused XLA scan and
    the kernel oracle; the Pallas kernel body is the traced-select twin
    (kernels/abc_sim.py) validated against this via ref.py parity tests.

    The channel axes are the trailing `weights.ndim` axes: [n_obs] as a
    rule, or [R, n_obs] for a regional scan that keeps its observed rows
    unflattened, summed in the same region-major order."""
    # spec is always a concrete SummarySpec here (only the Pallas kernel
    # needs traced selects), so non-cumulative specs skip the cum update
    # entirely — the carry passes through untouched. A cumulative channel's
    # bin value is its latest level (end-of-bin on flush days); a rate
    # channel's is the running within-bin sum.
    if spec.cumulative:
        cum = cum + x
        v = cum
        binv = v
    else:
        v = x
        binv = binv + v
    s = jnp.log1p(jnp.maximum(binv, 0.0)) if spec.log1p else binv
    diff = s - obs_t
    term = jnp.abs(diff) if kind.power == 1 else diff * diff
    chan_axes = tuple(range(-jnp.ndim(weights), 0))
    acc = acc + flush_t * jnp.sum(weights * term, axis=chan_axes)
    binv = binv * (1.0 - flush_t)
    return cum, binv, acc


def running_finalize(kind: DistanceKind, mean_scale: Array, acc: Array) -> Array:
    acc = acc * mean_scale
    return jnp.sqrt(acc) if kind.root else acc


def flush_columns(num_days: int, bin_days: int) -> np.ndarray:
    """Static day indices of the flush (bin-closing) columns, [n_bins] i64.

    These are the columns of the running-bin layout that hold actual summary
    values; everything else is an in-progress partial bin. bin_days == 1
    degenerates to every day."""
    t = np.arange(num_days)
    return t[((t + 1) % bin_days == 0) | (t == num_days - 1)]


def summary_features(
    spec: SummarySpec, series: Array, n_regions: int = 1
) -> Array:
    """Flatten a series to its summary FEATURE vector: [..., n_obs, T] ->
    [..., n_chan * n_bins].

    The conditioning-feature lowering used by the NPE backend
    (repro.core.npe): region-pool, apply the summary transform, then keep
    only the flush-day columns — exactly the values the running accumulator
    compares, so the features carry the same information the ABC distance
    sees. Applied identically to simulated batches ([B, n_obs, T]) and the
    observed side ([n_obs, T]); the flush-column gather is static (shape
    depends only on num_days/bin_days), so it traces under jit/vmap.
    """
    x = pool_channels(jnp.asarray(series, jnp.float32),
                      pool_factor(spec, n_regions), axis=-2)
    s = apply_summary(spec, x)
    cols = flush_columns(x.shape[-1], spec.bin_days)
    feats = s[..., cols]  # [..., n_chan, n_bins]
    return feats.reshape(feats.shape[:-2] + (-1,))


def summary_pairs() -> Tuple[Tuple[str, str], ...]:
    """Every registered (summary, distance) combination — the parity-test
    and benchmark sweep space."""
    return tuple(
        (s, d) for s in list_summaries() for d in sorted(DISTANCE_KINDS)
    )
