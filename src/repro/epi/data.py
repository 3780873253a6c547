"""Dataset registry for the epidemiology models.

The paper fits Johns Hopkins CSSE daily (A, R, D) series for Italy, New
Zealand and the USA, 49 days starting from the first day with 100 detected
cases. This container is offline, so we provide:

  * `synthetic_dataset(...)` — simulate a ground-truth trajectory from known
    parameters with ANY registered model spec. This is the scientifically
    strongest validation target: the ABC posterior must concentrate around
    the generating parameters (EXPERIMENTS.md claim C2).
  * Bundled demo series for italy / new_zealand / usa, generated from the
    paper's Table 8 posterior-mean parameters with fixed seeds and realistic
    (P, A0, R0, D0) starting points. These are clearly labeled approximations
    standing in for the JHU feed — NOT the actual JHU numbers. They are SIARD
    series (the paper model), but any model whose observed channels are
    (A, R, D) — e.g. seiard — can be fitted against them.

  * `usa_states` — a 51-region metapop_seir series over the 50 US states
    and the District of Columbia: per-state 2020 Census populations, the
    national `usa` day-0 counts split over the states by population, and a
    gravity mobility matrix between the state capitals (`gravity_mobility`).
    A generated stand-in for the JHU CSSE state-level feed, like the
    national series.

Every dataset is a `CountryData` with observed [n_observed, T] series; the
`model` field names the spec whose observed channels the rows correspond to.
A regional dataset may carry a geography: length-R populations and day-0
counts, and a row-stochastic [R][R] mobility matrix.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple, Union

import jax
import numpy as np

from repro.epi import engine
from repro.epi.models import get_model
from repro.epi.spec import (
    CompartmentalModel,
    EpiModelConfig,
    regionalize,
    validate_mobility,
)

#: a scalar, or a geography's per-region values (length R)
RegionValue = Union[float, Tuple[float, ...]]


@dataclasses.dataclass(frozen=True)
class CountryData:
    name: str
    #: total population, or a geography's length-R per-region populations
    population: RegionValue
    #: day-0 counts: scalars that seed the spec's `seed_region`, or a
    #: geography's length-R per-region counts
    a0: RegionValue
    r0: RegionValue
    d0: RegionValue
    observed: np.ndarray  # [n_observed, T] float32 — per-day observed channels
    #: tolerance the paper used for this dataset (Table 8), where applicable
    paper_tolerance: float | None = None
    #: generating parameters if synthetic, else None
    true_theta: Tuple[float, ...] | None = None
    synthetic: bool = True
    #: registry name of the model whose observed channels the rows match
    model: str = "siard"
    #: the observed channel names themselves — carried on the dataset so
    #: compatibility never needs a registry lookup (datasets may come from
    #: unregistered or since-replaced specs). Metapop datasets carry the
    #: region-major flattened labels ("I@r0", "R@r0", "I@r1", ...).
    observed_channels: Tuple[str, ...] = ("A", "R", "D")
    #: a geography's row-stochastic [R][R] mobility matrix, which replaces
    #: the spec's own unless the run's `ABCConfig.mobility` is set
    mobility: Tuple[Tuple[float, ...], ...] | None = None

    def __post_init__(self):
        per_region = [f for f in ("population", "a0", "r0", "d0")
                      if np.ndim(getattr(self, f))]
        sizes = {len(getattr(self, f)) for f in per_region}
        if self.mobility is not None:
            sizes.add(len(self.mobility))
        if len(sizes) > 1:
            raise ValueError(
                f"dataset {self.name!r}: the geography's per-region values "
                f"and mobility disagree on the region count ({sorted(sizes)})"
            )
        for f in per_region:
            object.__setattr__(
                self, f, tuple(float(x) for x in getattr(self, f)))
        if self.mobility is not None:
            object.__setattr__(self, "mobility",
                               validate_mobility(self.mobility, sizes.pop()))

    @property
    def regions(self) -> int | None:
        """Region count of the dataset's geography; None without one."""
        for value in (self.mobility, self.population, self.a0, self.r0,
                      self.d0):
            if np.ndim(value):
                return len(value)
        return None

    @property
    def num_days(self) -> int:
        return int(self.observed.shape[1])

    def model_config(self, num_days: int | None = None) -> EpiModelConfig:
        return EpiModelConfig(
            population=self.population,
            num_days=int(num_days or self.num_days),
            a0=self.a0,
            r0=self.r0,
            d0=self.d0,
        )

    def compatible_with(self, spec: CompartmentalModel) -> bool:
        """A spec can fit this dataset iff its observed channels line up
        (for metapop specs: the flattened per-region labels, so region
        count mismatches are caught too), and a geography has as many
        regions as the spec."""
        return (spec.observed_labels == self.observed_channels
                and self.regions in (None, spec.n_regions))


def synthetic_dataset(
    theta: Tuple[float, ...],
    population: RegionValue,
    num_days: int = 49,
    a0: RegionValue = 100.0,
    r0: RegionValue = 0.0,
    d0: RegionValue = 0.0,
    seed: int = 0,
    name: str = "synthetic",
    paper_tolerance: float | None = None,
    model: Union[str, CompartmentalModel] = "siard",
    schedule=None,
    mobility=None,
) -> CountryData:
    """Generate a ground-truth dataset by simulating with known parameters.

    `schedule` (an InterventionSchedule with FIXED scales) generates the
    series under a known intervention — e.g. a mid-horizon contact-rate drop
    — which is the validation target for intervention-aware inference.
    `theta` is the base parameter vector; the schedule's pinned scales are
    appended automatically (pass a full widened theta to override).

    For a metapop `model`, length-R `population` / day-0 counts and a
    `mobility` matrix make a dataset with a geography, simulated with them.
    """
    spec = get_model(model)
    cfg = EpiModelConfig(
        population=population, num_days=num_days, a0=a0, r0=r0, d0=d0
    )
    th = np.asarray([theta], np.float32)
    width = spec.n_params
    if schedule is not None and not schedule.is_empty:
        width = schedule.param_width(spec)
        if th.shape[1] == spec.n_params:
            scales = np.asarray(
                [s for row in schedule.fixed_scales() for s in row],
                np.float32,
            )
            th = np.concatenate([th, scales[None, :]], axis=1)
    if th.shape[1] != width:
        raise ValueError(
            f"theta has {th.shape[1]} entries; model {spec.name!r} "
            f"expects {width}"
        )
    if mobility is not None:
        mobility = validate_mobility(mobility, spec.n_regions)
    obs = engine.simulate_observed(
        spec, th, jax.random.PRNGKey(seed), cfg, schedule, mobility=mobility
    )[0]
    return CountryData(
        name=name,
        population=population,
        a0=a0,
        r0=r0,
        d0=d0,
        observed=np.asarray(obs, np.float32),
        paper_tolerance=paper_tolerance,
        true_theta=tuple(float(x) for x in theta),
        synthetic=True,
        model=spec.name,
        observed_channels=spec.observed_labels,
        mobility=mobility,
    )


# Paper Table 8 posterior means (100-sample rows) — used as generating
# parameters for the bundled demo series.
_TABLE8_THETA = {
    "italy": (0.384, 36.054, 0.595, 0.013, 0.385, 0.009, 0.477, 0.830),
    "new_zealand": (0.474, 46.603, 1.223, 0.030, 0.499, 0.001, 0.520, 1.198),
    "usa": (0.329, 10.667, 0.322, 0.007, 0.435, 0.005, 0.490, 0.716),
}

# (population, A0, R0, D0, paper tolerance, seed)
_COUNTRY_META = {
    "italy": (60.36e6, 155.0, 2.0, 3.0, 5e4, 1),
    "new_zealand": (4.917e6, 102.0, 0.0, 0.0, 1250.0, 2),
    "usa": (328.2e6, 104.0, 7.0, 6.0, 2e5, 3),
}

#: generating parameters for the per-model synthetic_small problem. SIARD
#: keeps its historical values so existing tolerances/baselines stay valid;
#: other models use their spec's default_theta.
_SYNTH_SMALL_THETA = {"siard": (0.4, 30.0, 0.8, 0.05, 0.3, 0.01, 0.5, 1.0)}

#: the 50 US states and the District of Columbia in FIPS order: postal
#: code, resident population (US Census Bureau, 2020 Census Apportionment
#: Results, April 2021, Table 2; the sum is the published 331,449,281) and
#: the capital's latitude and longitude in degrees (approximate, to 0.01)
US_STATES = (
    ("AL", 5024279, 32.38, -86.30), ("AK", 733391, 58.30, -134.42),
    ("AZ", 7151502, 33.45, -112.07), ("AR", 3011524, 34.75, -92.29),
    ("CA", 39538223, 38.58, -121.49), ("CO", 5773714, 39.74, -104.99),
    ("CT", 3605944, 41.76, -72.68), ("DE", 989948, 39.16, -75.52),
    ("DC", 689545, 38.91, -77.04), ("FL", 21538187, 30.44, -84.28),
    ("GA", 10711908, 33.75, -84.39), ("HI", 1455271, 21.31, -157.86),
    ("ID", 1839106, 43.62, -116.20), ("IL", 12812508, 39.78, -89.65),
    ("IN", 6785528, 39.77, -86.16), ("IA", 3190369, 41.59, -93.62),
    ("KS", 2937880, 39.05, -95.68), ("KY", 4505836, 38.20, -84.87),
    ("LA", 4657757, 30.45, -91.19), ("ME", 1362359, 44.31, -69.78),
    ("MD", 6177224, 38.98, -76.49), ("MA", 7029917, 42.36, -71.06),
    ("MI", 10077331, 42.73, -84.56), ("MN", 5706494, 44.95, -93.09),
    ("MS", 2961279, 32.30, -90.18), ("MO", 6154913, 38.58, -92.17),
    ("MT", 1084225, 46.59, -112.04), ("NE", 1961504, 40.81, -96.70),
    ("NV", 3104614, 39.16, -119.77), ("NH", 1377529, 43.21, -71.54),
    ("NJ", 9288994, 40.22, -74.76), ("NM", 2117522, 35.69, -105.94),
    ("NY", 20201249, 42.65, -73.76), ("NC", 10439388, 35.78, -78.64),
    ("ND", 779094, 46.81, -100.78), ("OH", 11799448, 39.96, -83.00),
    ("OK", 3959353, 35.47, -97.52), ("OR", 4237256, 44.94, -123.04),
    ("PA", 13002700, 40.27, -76.88), ("RI", 1097379, 41.82, -71.41),
    ("SC", 5118425, 34.00, -81.03), ("SD", 886667, 44.37, -100.35),
    ("TN", 6910840, 36.16, -86.78), ("TX", 29145505, 30.27, -97.74),
    ("UT", 3271616, 40.76, -111.89), ("VT", 643077, 44.26, -72.58),
    ("VA", 8631393, 37.54, -77.44), ("WA", 7705281, 47.04, -122.90),
    ("WV", 1793716, 38.35, -81.63), ("WI", 5893718, 43.07, -89.40),
    ("WY", 576851, 41.14, -104.82),
)

#: the `usa_states` stand-in: metapop_seir's default_theta, the national
#: `usa` series' day-0 A0 and R0 split over the states, a gravity matrix
#: with the classical exponents (b = 1, c = 2) and eps = 0.04, about the
#: share of US workers who work outside their state of residence
_USA_STATES_META = {"a0": 104, "r0": 7, "b": 1.0, "c": 2.0, "eps": 0.04,
                    "seed": 51}

#: mean Earth radius (km) of the haversine distance
EARTH_RADIUS_KM = 6371.0


def haversine_km(coords) -> np.ndarray:
    """[R, R] great-circle distances (km, float64) between the (latitude,
    longitude) points `coords`, in degrees."""
    lat, lon = np.radians(np.asarray(coords, np.float64)).T
    h = (np.sin((lat[:, None] - lat[None, :]) / 2.0) ** 2
         + np.cos(lat[:, None]) * np.cos(lat[None, :])
         * np.sin((lon[:, None] - lon[None, :]) / 2.0) ** 2)
    return 2.0 * EARTH_RADIUS_KM * np.arcsin(np.sqrt(h))


def gravity_mobility(populations, coords, b: float = 1.0, c: float = 2.0,
                     eps: float = 0.04) -> Tuple[Tuple[float, ...], ...]:
    """Row-stochastic gravity coupling between regions.

    M[i][i] = 1 - eps; off the diagonal region i spreads eps over the others
    in proportion to P_j^b / d_ij^c (P_i cancels in a row), d_ij the
    haversine distance between the regions' points. Computed in float64 and
    rounded once to float32."""
    pop = np.asarray(populations, np.float64)
    dist = haversine_km(coords)
    if (dist[~np.eye(pop.size, dtype=bool)] <= 0.0).any():
        raise ValueError("two regions share a point: gravity needs d_ij > 0")
    np.fill_diagonal(dist, np.inf)  # no weight on a region's own row entry
    weight = pop[None, :] ** b / dist ** c
    m = eps * weight / weight.sum(axis=1, keepdims=True)
    np.fill_diagonal(m, 1.0 - eps)
    return validate_mobility(m.astype(np.float32).tolist(), pop.size)


def split_by_population(total: int, populations) -> Tuple[float, ...]:
    """`total` whole counts over regions in proportion to population, by
    largest remainder (ties go to the earlier region)."""
    pop = [int(p) for p in populations]
    whole = sum(pop)
    counts = [total * p // whole for p in pop]
    remainders = [total * p % whole for p in pop]
    order = sorted(range(len(pop)), key=lambda i: -remainders[i])
    for i in order[: total - sum(counts)]:
        counts[i] += 1
    return tuple(float(x) for x in counts)


def usa_states_model() -> CompartmentalModel:
    """The spec `usa_states` is generated with: metapop_seir on 51 regions
    (the dataset's own gravity matrix replaces the spec's identity)."""
    return regionalize(get_model("metapop_seir"), len(US_STATES))


def _usa_states(num_days: int, spec: CompartmentalModel) -> CountryData:
    meta = _USA_STATES_META
    pops = [p for _, p, _, _ in US_STATES]
    return synthetic_dataset(
        theta=spec.default_theta,
        population=tuple(float(p) for p in pops),
        num_days=num_days,
        a0=split_by_population(meta["a0"], pops),
        r0=split_by_population(meta["r0"], pops),
        d0=0.0,
        seed=meta["seed"],
        name="usa_states",
        model=spec,
        mobility=gravity_mobility(
            pops, [(lat, lon) for _, _, lat, lon in US_STATES],
            meta["b"], meta["c"], meta["eps"]),
    )


_CACHE: Dict[tuple, CountryData] = {}


def list_datasets() -> Tuple[str, ...]:
    return tuple(sorted(_COUNTRY_META)) + ("synthetic_small", "usa_states")


def list_countries() -> Tuple[str, ...]:
    """The bundled country series (the paper's three-country study grid) —
    the default dataset axis of a campaign (repro.core.campaign)."""
    return tuple(sorted(_COUNTRY_META))


def get_dataset(
    name: str,
    num_days: int = 49,
    model: Union[str, CompartmentalModel] = "siard",
) -> CountryData:
    """Fetch a bundled dataset by name ('italy' | 'new_zealand' | 'usa' |
    'synthetic_small' | 'usa_states').

    `model` selects which registry spec generates (and is fitted against)
    the series. The bundled country series are SIARD-generated; they can be
    requested for any model with matching observed channels (e.g. seiard).
    `usa_states` is generated by `usa_states_model()` and fits any 51-region
    spec that observes (I, R), such as `regionalize(metapop_seir, 51)`.
    """
    spec = get_model(model)
    # key on the spec object itself (hashable by design), not its name: two
    # different unregistered specs sharing a name must not alias cached data
    key = (name, num_days, spec)
    if key in _CACHE:
        return _CACHE[key]
    if name == "synthetic_small":
        # A tiny, fast-converging problem for tests / quickstart: small
        # population keeps distances small so moderate tolerances accept.
        ds = synthetic_dataset(
            theta=_SYNTH_SMALL_THETA.get(spec.name, spec.default_theta),
            population=1e6,
            num_days=num_days,
            a0=100.0,
            seed=7,
            name="synthetic_small",
            paper_tolerance=None,
            model=spec,
        )
    elif name == "usa_states":
        base_spec = usa_states_model()
        if spec.observed_labels != base_spec.observed_labels:
            raise ValueError(
                f"dataset {name!r} holds (I, R) series of "
                f"{base_spec.n_regions} regions; model {spec.name!r} "
                f"observes {spec.n_regions} region(s) of {spec.observed}"
            )
        if spec == base_spec:
            ds = _usa_states(num_days, spec)
        else:
            base = get_dataset(name, num_days=num_days, model=base_spec)
            ds = dataclasses.replace(base, model=spec.name, true_theta=None)
    elif name in _COUNTRY_META:
        if spec.name != "siard":
            # the series stays SIARD-generated; re-tag the cached siard entry
            # (no re-simulation) iff the requested model observes the same
            # channels and can therefore fit it
            base = get_dataset(name, num_days=num_days, model="siard")
            if not base.compatible_with(spec):
                raise ValueError(
                    f"dataset {name!r} holds (A, R, D) series; model "
                    f"{spec.name!r} observes {spec.observed_labels}"
                )
            ds = dataclasses.replace(base, model=spec.name, true_theta=None)
        else:
            population, a0, r0, d0, tol, seed = _COUNTRY_META[name]
            # demo series: generated from the paper's posterior means,
            # standing in for the (offline) JHU feed.
            ds = synthetic_dataset(
                theta=_TABLE8_THETA[name],
                population=population,
                num_days=num_days,
                a0=a0,
                r0=r0,
                d0=d0,
                seed=seed,
                name=name,
                paper_tolerance=tol,
                model="siard",
            )
    else:
        raise KeyError(f"unknown dataset {name!r}; available: {list_datasets()}")
    _CACHE[key] = ds
    return ds
