"""Declarative specification of a stochastic compartmental model.

A `CompartmentalModel` is the single source of truth every layer consumes:

  * the reference tau-leap engine (`repro.epi.engine`) — pure jax.numpy,
  * the fused Pallas kernel (`repro.kernels.abc_sim`) — the spec's hazards and
    stoichiometry are inlined into the kernel body at trace time,
  * the ABC/SMC drivers (`repro.core.abc`, `repro.core.smc`) — prior bounds,
    parameter names and output shapes all derive from the spec,
  * datasets (`repro.epi.data`) — synthetic ground truth is simulated from
    `default_theta` with the spec's own dynamics.

The spec is declarative: compartments and parameters are *names*, transitions
are a stoichiometry matrix plus a hazard function, and the initial state is a
rule mapping parameters to compartment counts. Dynamics follow the paper's
tau-leap scheme (§2.1, steps 2-4) generically:

    h   = hazard_rows(state, theta)              one rate per transition
    n_k = floor(Normal(h_k, sqrt(h_k)))          Gaussian tau-leap counts
    n_k = clip(n_k, 0, remaining[source_k])      sequential source draining
    x'  = x + stoichiometry^T @ n                apply transitions

Sequential source draining means transitions are clamped in declaration
order, each one reducing the budget of its source compartment, so no
compartment ever goes negative and total mass is conserved exactly — the
same clamping contract the paper's IPU implementation applies (its cycle
table shows `Clamp` compute sets).

Layout contract for `hazard_rows` / `initial_rows`: they receive the state
and parameters as *sequences of channel arrays* (one array per compartment /
parameter) rather than stacked tensors. The same function body therefore
runs unchanged in the reference engine (channels are slices of a [..., n]
tensor) and inside the Pallas kernel (channels are (1, TILE) VREG rows).

Known limitation: the seeding interface is the paper's three scalars
(a0, r0, d0) — `initial_rows` receives exactly those, and the kernel's
constant layout reserves the same three slots. Models are free to
reinterpret them (SIR/SEIR treat a0 as a generic day-0 case count), but a
model needing MORE day-0 inputs requires widening `InitialFn`, the fconsts
layout in kernels/abc_sim.py and `CountryData` together.

Spatial metapopulation models: a spec may declare `n_regions` (R) copies of
its compartments coupled through a row-stochastic `mobility` matrix. State,
transitions and observed channels then flatten region-major — channel
`r * n_state + c` is compartment c of region r — and every layer (engine,
fused scan, Pallas kernel, summaries, datasets) consumes that layout through
the `total_*` properties, with R=1 degenerating bit-identically to the flat
single-population layout. Hazards see coupling through the `coupled` field:
for each named compartment, the engine appends one EXTRA state row per
region holding the mobility-weighted mass sum_q mobility[r][q] * x_q, so a
metapop-aware `hazard_rows` receives n_state local rows followed by
len(coupled) coupled rows and stays row-level (the same body runs in the XLA
engine, where rows carry a trailing region axis, and in the Pallas kernel,
where regions unroll into separate VREG rows at trace time). Without a
geography each region holds population / R people and the dataset's scalar
(a0, r0, d0) day-0 counts seed `seed_region` only, every other region
starting fully susceptible. A dataset with a geography (`CountryData` with
length-R population and day-0 counts, and its own mobility matrix) gives
each region its own population P_r and seeds, and its matrix replaces the
spec's unless `ABCConfig.mobility` is set; its coupled rows then carry the
prevalence of the places region r's contacts happen, at region r's scale:
P_r * sum_q mobility[r][q] * x_q / P_q (`engine.coupling_matrix`).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Sequence, Tuple

Rows = Sequence  # sequence of same-shape arrays, one per channel

#: (state_rows, param_rows, population) -> one rate array per transition;
#: metapop-aware hazards additionally receive len(coupled) coupled-mass rows
#: appended to state_rows
HazardFn = Callable[[Rows, Rows, object], Tuple]
#: (param_rows, population, a0, r0, d0) -> one array per compartment
InitialFn = Callable[[Rows, object, object, object, object], Tuple]

#: tolerance for row-stochasticity of mobility rows (f32 inputs)
_ROW_SUM_TOL = 1e-5


def identity_mobility(n_regions: int) -> Tuple[Tuple[float, ...], ...]:
    """The zero-coupling matrix: every region keeps all of its own mass."""
    return tuple(
        tuple(1.0 if q == r else 0.0 for q in range(n_regions))
        for r in range(n_regions)
    )


def validate_mobility(mobility, n_regions: int) -> Tuple[Tuple[float, ...], ...]:
    """Normalize + validate a mobility matrix: [R][R], non-negative rows each
    summing to 1 (row-stochastic). Raises a loud ValueError otherwise."""
    rows = tuple(tuple(float(x) for x in row) for row in mobility)
    if len(rows) != n_regions or any(len(r) != n_regions for r in rows):
        raise ValueError(
            f"mobility must be a [{n_regions}][{n_regions}] matrix, got "
            f"shape ({len(rows)}, {tuple(len(r) for r in rows)})"
        )
    for r, row in enumerate(rows):
        if any(x < 0.0 for x in row):
            raise ValueError(
                f"mobility row {r} has negative entries: {row} — rows must "
                "be non-negative probabilities"
            )
        s = sum(row)
        if abs(s - 1.0) > _ROW_SUM_TOL:
            raise ValueError(
                f"mobility row {r} sums to {s!r}, not 1: mobility must be "
                "row-stochastic (each region's mass weights sum to 1)"
            )
    return rows


def make_mobility(spec: str, n_regions: int) -> Tuple[Tuple[float, ...], ...]:
    """Build a mobility matrix from the CLI grammar (--mobility):

      * "identity"     — no inter-region coupling (block-diagonal dynamics)
      * "uniform:EPS"  — each region keeps 1-EPS, spreads EPS evenly over
                         the other R-1 regions (fully-mixed gravity-free)
      * "ring:EPS"     — each region keeps 1-EPS, sends EPS/2 to each ring
                         neighbour (1-D lattice with wraparound)
    """
    kind, _, arg = spec.partition(":")
    if kind == "identity":
        if arg:
            raise ValueError(f"identity mobility takes no argument: {spec!r}")
        return identity_mobility(n_regions)
    if kind not in ("uniform", "ring"):
        raise ValueError(
            f"unknown mobility kind {spec!r}; grammar: identity | "
            "uniform:EPS | ring:EPS"
        )
    if not arg:
        raise ValueError(f"mobility {kind!r} needs a coupling strength: {spec!r}")
    eps = float(arg)
    if not 0.0 <= eps <= 1.0:
        raise ValueError(f"mobility coupling must be in [0, 1], got {eps}")
    if n_regions == 1:
        return identity_mobility(1)
    rows = []
    for r in range(n_regions):
        row = [0.0] * n_regions
        row[r] = 1.0 - eps
        if kind == "uniform":
            for q in range(n_regions):
                if q != r:
                    row[q] = eps / (n_regions - 1)
        else:  # ring
            if n_regions == 2:
                row[(r + 1) % 2] = eps
            else:
                row[(r - 1) % n_regions] += eps / 2.0
                row[(r + 1) % n_regions] += eps / 2.0
        rows.append(tuple(row))
    return validate_mobility(rows, n_regions)


@dataclasses.dataclass(frozen=True)
class CompartmentalModel:
    """Declarative spec of a stochastic compartmental epidemic model.

    Frozen and hashable (fields are tuples / callables), so a model can be a
    `static_argnames` entry of a jitted function — the Pallas kernel builder
    relies on this to specialize the kernel body per model.
    """

    name: str
    compartments: Tuple[str, ...]
    param_names: Tuple[str, ...]
    #: uniform-box prior upper bounds, one per parameter (lows default to 0)
    prior_highs: Tuple[float, ...]
    #: stoichiometry matrix [n_transitions][n_state]: each row moves one unit
    #: of mass out of exactly one source (-1) into one destination (+1)
    stoichiometry: Tuple[Tuple[int, ...], ...]
    #: names of observed compartments, compared against data [n_observed, T]
    observed: Tuple[str, ...]
    hazard_rows: HazardFn
    initial_rows: InitialFn
    #: plausible generating parameters — used for synthetic ground-truth data
    default_theta: Tuple[float, ...]
    prior_lows: Tuple[float, ...] | None = None
    doc: str = ""
    #: spatial metapopulation: number of coupled regions sharing these
    #: dynamics; R=1 (the default) is the flat single-population layout
    n_regions: int = 1
    #: row-stochastic [R][R] coupling matrix — mobility[r][q] weights region
    #: q's mass in region r's coupled rows. None defaults to the identity
    #: (zero coupling) whenever regions or coupled compartments are declared.
    mobility: Tuple[Tuple[float, ...], ...] | None = None
    #: compartments whose mobility-weighted mass rows are appended to the
    #: state rows seen by hazard_rows (in this order) — a metapop-aware
    #: hazard reads its force-of-infection mass from these instead of the
    #: local rows
    coupled: Tuple[str, ...] = ()
    #: region seeded with the dataset's scalar (a0, r0, d0) day-0 counts;
    #: all other regions start fully susceptible at population / n_regions.
    #: Unused with a geography, whose counts and populations are per region.
    seed_region: int = 0

    def __post_init__(self):
        ns, np_, nt = len(self.compartments), len(self.param_names), len(self.stoichiometry)
        if len(self.prior_highs) != np_:
            raise ValueError(f"{self.name}: prior_highs must have {np_} entries")
        if self.prior_lows is not None and len(self.prior_lows) != np_:
            raise ValueError(f"{self.name}: prior_lows must have {np_} entries")
        if len(self.default_theta) != np_:
            raise ValueError(f"{self.name}: default_theta must have {np_} entries")
        for k, row in enumerate(self.stoichiometry):
            if len(row) != ns:
                raise ValueError(f"{self.name}: stoichiometry row {k} has wrong width")
            if sum(row) != 0:
                raise ValueError(
                    f"{self.name}: transition {k} does not conserve mass: {row}"
                )
            if sorted(row) != sorted((-1, 1) + (0,) * (ns - 2)):
                raise ValueError(
                    f"{self.name}: transition {k} must move one unit from one "
                    f"source to one destination, got {row}"
                )
        for name in self.observed:
            if name not in self.compartments:
                raise ValueError(f"{self.name}: observed {name!r} is not a compartment")
        if nt > 8:
            # the counter-based RNG reserves 8 counter slots per day PER
            # REGION at R=1 (kernels/rng.day_transition_ctr); metapop models
            # widen the per-day stride via `ctr_slots`, but the per-region
            # transition count stays capped
            raise ValueError(f"{self.name}: at most 8 transitions supported, got {nt}")
        # ---- spatial metapopulation fields ----
        if not isinstance(self.n_regions, int) or self.n_regions < 1:
            raise ValueError(
                f"{self.name}: n_regions must be a positive int, got "
                f"{self.n_regions!r}"
            )
        object.__setattr__(self, "coupled", tuple(self.coupled))
        for name in self.coupled:
            if name not in self.compartments:
                raise ValueError(
                    f"{self.name}: coupled {name!r} is not a compartment"
                )
        if not 0 <= self.seed_region < self.n_regions:
            raise ValueError(
                f"{self.name}: seed_region {self.seed_region} out of range "
                f"for {self.n_regions} regions"
            )
        if self.mobility is None:
            if self.coupled or self.n_regions > 1:
                object.__setattr__(
                    self, "mobility", identity_mobility(self.n_regions)
                )
        else:
            object.__setattr__(
                self, "mobility", validate_mobility(self.mobility, self.n_regions)
            )

    # ------------------------------------------------------------ dimensions
    @property
    def n_state(self) -> int:
        return len(self.compartments)

    @property
    def n_params(self) -> int:
        return len(self.param_names)

    @property
    def n_transitions(self) -> int:
        return len(self.stoichiometry)

    @property
    def n_observed(self) -> int:
        return len(self.observed)

    @property
    def observed_idx(self) -> Tuple[int, ...]:
        return tuple(self.compartments.index(c) for c in self.observed)

    @property
    def transition_sources(self) -> Tuple[int, ...]:
        """Source compartment index of each transition (the -1 entry)."""
        return tuple(row.index(-1) for row in self.stoichiometry)

    # ------------------------------------------------- region-major totals
    # The flattened metapop layout: channel r * n_state + c is compartment c
    # of region r; transitions and observed channels flatten the same way.
    # At R=1 every total_* equals its per-region counterpart, so generic
    # layers index with these unconditionally.
    @property
    def total_state(self) -> int:
        return self.n_regions * self.n_state

    @property
    def total_transitions(self) -> int:
        return self.n_regions * self.n_transitions

    @property
    def total_observed(self) -> int:
        return self.n_regions * self.n_observed

    @property
    def total_observed_idx(self) -> Tuple[int, ...]:
        """Observed channel indices into the region-major flattened state."""
        local = self.observed_idx
        return tuple(
            r * self.n_state + c for r in range(self.n_regions) for c in local
        )

    @property
    def observed_labels(self) -> Tuple[str, ...]:
        """Per-channel labels of the flattened observed layout (dataset
        rows): the plain compartment names at R=1, `C@rN` per region else."""
        if self.n_regions == 1:
            return self.observed
        return tuple(
            f"{c}@r{r}" for r in range(self.n_regions) for c in self.observed
        )

    @property
    def coupled_idx(self) -> Tuple[int, ...]:
        return tuple(self.compartments.index(c) for c in self.coupled)

    @property
    def is_regional(self) -> bool:
        """True when the spec leaves the flat R=1 uncoupled layout — the
        engine/kernel then take the generalized region paths."""
        return self.n_regions > 1 or bool(self.coupled)

    @property
    def ctr_slots(self) -> int:
        """Per-day counter stride of the hash RNG: 8 at R=1 (the legacy
        layout, bit-identity-critical), widened in sublane-sized steps for
        metapop models whose total transition count exceeds it."""
        return max(8, -(-self.total_transitions // 8) * 8)

    # ------------------------------------------------------------------ misc
    def prior(self):
        """The model's uniform box prior U(lows, highs)."""
        from repro.core.priors import UniformBoxPrior

        return UniformBoxPrior(highs=self.prior_highs, lows=self.prior_lows)

    def describe(self) -> str:
        lines = [
            f"model {self.name}: {self.n_state} compartments "
            f"({', '.join(self.compartments)}), {self.n_params} params, "
            f"{self.n_transitions} transitions, observed ({', '.join(self.observed)})"
        ]
        if self.n_regions > 1:
            lines[0] += f", {self.n_regions} regions"
        for row, src in zip(self.stoichiometry, self.transition_sources):
            dst = row.index(1)
            lines.append(f"  {self.compartments[src]} -> {self.compartments[dst]}")
        if self.coupled:
            lines.append(f"  coupled mass rows: {', '.join(self.coupled)}")
        return "\n".join(lines)


def regionalize(
    model: CompartmentalModel,
    n_regions: int,
    mobility=None,
    name: str | None = None,
    seed_region: int = 0,
) -> CompartmentalModel:
    """A spatial variant of `model` with R regions coupled by `mobility`.

    `mobility` is a matrix, a `make_mobility` grammar string ("ring:0.1") or
    None (identity). The per-region dynamics are unchanged; only metapop-
    aware models (non-empty `coupled`) actually exchange mass — regionalizing
    an uncoupled model yields R independent copies, useful for scaling
    studies. Validation (row-stochasticity, shape) happens in the spec's
    __post_init__ and fails loudly.
    """
    if isinstance(mobility, str):
        mobility = make_mobility(mobility, n_regions)
    return dataclasses.replace(
        model,
        name=name or (model.name if n_regions == model.n_regions
                      else f"{model.name}_r{n_regions}"),
        n_regions=n_regions,
        mobility=mobility,
        seed_region=seed_region,
    )


class ScheduleShape(NamedTuple):
    """The compile-relevant part of an intervention schedule.

    Two schedules with the same shape — same window count, same set of scaled
    parameters — compile to the same kernel / wave loop: the breakpoint DAYS
    and the per-window SCALES are runtime values (traced scalars / extra theta
    columns), not constants. Campaigns rely on this to sweep lockdown-day x
    scale grids with one compilation.
    """

    n_windows: int
    tv_indices: Tuple[int, ...]  # positions of the scaled params in param_names

    @property
    def n_tv(self) -> int:
        return len(self.tv_indices)

    @property
    def n_scales(self) -> int:
        return self.n_windows * self.n_tv


@dataclasses.dataclass(frozen=True)
class InterventionSchedule:
    """Piecewise-constant time-varying scaling of selected hazard parameters.

    Models policy changes (lockdowns, reopenings) as per-window multiplicative
    scales on a subset of the model's parameters. Day d falls in window
    `w = #{i : d >= breakpoints[i]}`: window 0 (before the first breakpoint)
    always uses the base parameters unscaled; window w >= 1 multiplies each
    parameter named in `tv_params` by that window's scale factor.

    The scales are ordinary inference parameters: theta widens from
    [n_params] to [n_params + n_windows * n_tv], laid out as the base
    parameters followed by window-major scale blocks
    (w1: tv_0..tv_{n_tv-1}, w2: ..., ...). Each scale gets a uniform box
    prior [scale_lows[w][j], scale_highs[w][j]]; a zero-width box
    (low == high) pins the scale to a known value — that is how fixed
    counterfactual scenarios ("alpha drops to 0.3 on day 20") are expressed
    without a separate code path.

    Frozen and hashable, so a schedule can ride along static jit arguments
    (the Pallas kernel builder keys on `shape(model)` only, see ScheduleShape).
    """

    #: names of the scaled ("time-varying") parameters, subset of param_names
    tv_params: Tuple[str, ...]
    #: strictly increasing, positive day indices; window i+1 starts at day
    #: breakpoints[i]. n_windows == len(breakpoints).
    breakpoints: Tuple[int, ...]
    #: per-window scale prior bounds, [n_windows][n_tv]
    scale_lows: Tuple[Tuple[float, ...], ...]
    scale_highs: Tuple[Tuple[float, ...], ...]

    def __post_init__(self):
        object.__setattr__(self, "tv_params", tuple(self.tv_params))
        object.__setattr__(
            self, "breakpoints", tuple(int(b) for b in self.breakpoints)
        )
        object.__setattr__(
            self,
            "scale_lows",
            tuple(tuple(float(x) for x in row) for row in self.scale_lows),
        )
        object.__setattr__(
            self,
            "scale_highs",
            tuple(tuple(float(x) for x in row) for row in self.scale_highs),
        )
        nw, nt = len(self.breakpoints), len(self.tv_params)
        if nw and not nt:
            raise ValueError("schedule has breakpoints but no tv_params")
        if nt and not nw:
            raise ValueError("schedule has tv_params but no breakpoints")
        if any(b <= 0 for b in self.breakpoints):
            raise ValueError(f"breakpoints must be positive days: {self.breakpoints}")
        if any(
            b2 <= b1 for b1, b2 in zip(self.breakpoints, self.breakpoints[1:])
        ):
            raise ValueError(
                f"breakpoints must be strictly increasing: {self.breakpoints}"
            )
        if len(self.scale_lows) != nw or len(self.scale_highs) != nw:
            raise ValueError(f"need {nw} scale bound rows, one per window")
        for lo_row, hi_row in zip(self.scale_lows, self.scale_highs):
            if len(lo_row) != nt or len(hi_row) != nt:
                raise ValueError(f"each scale bound row must have {nt} entries")
            if any(h < l for l, h in zip(lo_row, hi_row)):
                raise ValueError("scale_highs must be >= scale_lows")
        if nw > 16:
            # the kernel packs breakpoints into iconst lanes 1..n_windows;
            # 16 is far beyond any realistic policy timeline
            raise ValueError(f"at most 16 intervention windows supported, got {nw}")

    # ------------------------------------------------------------ constructors
    @staticmethod
    def fixed(tv_params, breakpoints, scales) -> "InterventionSchedule":
        """Known (counterfactual) scales: `scales` is [n_windows][n_tv], or a
        flat [n_windows] sequence when there is a single tv param."""
        rows = tuple(
            (float(s),) if not isinstance(s, (tuple, list)) else tuple(s)
            for s in scales
        )
        return InterventionSchedule(
            tv_params=tuple(tv_params),
            breakpoints=tuple(breakpoints),
            scale_lows=rows,
            scale_highs=rows,
        )

    @staticmethod
    def inferred(
        tv_params, breakpoints, low: float = 0.0, high: float = 2.0
    ) -> "InterventionSchedule":
        """Unknown scales, inferred by ABC under U(low, high) per window."""
        nt = len(tuple(tv_params))
        return InterventionSchedule(
            tv_params=tuple(tv_params),
            breakpoints=tuple(breakpoints),
            scale_lows=tuple((float(low),) * nt for _ in breakpoints),
            scale_highs=tuple((float(high),) * nt for _ in breakpoints),
        )

    # ------------------------------------------------------------- dimensions
    @property
    def n_windows(self) -> int:
        return len(self.breakpoints)

    @property
    def n_tv(self) -> int:
        return len(self.tv_params)

    @property
    def n_scales(self) -> int:
        return self.n_windows * self.n_tv

    @property
    def is_empty(self) -> bool:
        return self.n_windows == 0

    def shape(self, model: CompartmentalModel) -> ScheduleShape:
        """Static (compile-key) part; validates tv_params against the model."""
        idx = []
        for name in self.tv_params:
            if name not in model.param_names:
                raise ValueError(
                    f"schedule scales {name!r}, which is not a parameter of "
                    f"model {model.name!r} ({model.param_names})"
                )
            idx.append(model.param_names.index(name))
        return ScheduleShape(n_windows=self.n_windows, tv_indices=tuple(idx))

    def param_width(self, model: CompartmentalModel) -> int:
        return model.n_params + self.n_scales

    def scale_param_names(self) -> Tuple[str, ...]:
        """Names of the widened theta columns, window-major: alpha_w1, ..."""
        return tuple(
            f"{p}_w{w + 1}"
            for w in range(self.n_windows)
            for p in self.tv_params
        )

    def param_names(self, model: CompartmentalModel) -> Tuple[str, ...]:
        return model.param_names + self.scale_param_names()

    def fixed_scales(self) -> Tuple[Tuple[float, ...], ...]:
        """The pinned scale values; raises if any window's scales are inferred."""
        for lo_row, hi_row in zip(self.scale_lows, self.scale_highs):
            if any(h > l for l, h in zip(lo_row, hi_row)):
                raise ValueError(
                    "schedule has inferred (non-degenerate) scale priors; "
                    "fixed_scales() needs every low == high"
                )
        return self.scale_lows

    def tag(self) -> str:
        """Compact filesystem-safe label for scenario/checkpoint names."""
        if self.is_empty:
            return "none"
        wins = []
        for w, b in enumerate(self.breakpoints):
            parts = []
            for l, h in zip(self.scale_lows[w], self.scale_highs[w]):
                parts.append(f"{l:g}" if l == h else f"{l:g}to{h:g}")
            wins.append(f"d{b}s" + "+".join(parts))
        return "iv_" + "+".join(self.tv_params) + "_" + "_".join(wins)


#: the canonical no-op schedule — simulating under it is bit-identical to
#: passing schedule=None (pinned by tests/test_interventions.py)
EMPTY_SCHEDULE = InterventionSchedule(
    tv_params=(), breakpoints=(), scale_lows=(), scale_highs=()
)


@dataclasses.dataclass(frozen=True)
class EpiModelConfig:
    """Static simulation configuration (shared across all registry models)."""

    # P — total population at day 0, or a geography's length-R populations
    # (metapop specs only; see repro.epi.engine.region_population)
    population: float
    num_days: int  # T — simulation horizon (paper uses 49 for fitting)
    # initial observed values (A0, R0, D0) at day 0; the spec's initial-state
    # rule decides how they seed the compartments. A geography gives them
    # per region, as length-R sequences.
    a0: float = 100.0
    r0: float = 0.0
    d0: float = 0.0
