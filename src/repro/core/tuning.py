"""Roofline-instrumented autotuning of the fused ABC hot path.

The paper's headline claim is a roofline argument: the IPU's 30x over a Xeon
comes from where the simulation's working set sits relative to the memory
hierarchy. This module closes the loop between that analytic story and the
code we actually run, in three layers:

  1. **Analytic cost model** (`cost_model`) — FLOPs and HBM bytes per
     sample-day for ANY `(CompartmentalModel, schedule, summary, distance)`
     combination. Nothing is hardwired to the paper's SIARD constants: the
     per-day op count is obtained by tracing ONE day of the oracle dynamics
     (the spec's own `hazard_rows`, the shared counter RNG, the generic
     tau-leap clamp and the running summary accumulator) with
     `jax.make_jaxpr` and counting arithmetic primitives, so the number is
     derived from the spec itself and stays correct when a new model is
     registered (cross-checked against full `kernels/ref.py` traces in
     tests/test_tuning.py). The byte model is closed-form from the spec's
     shape: the fused kernel reads `theta_width` floats and writes one
     distance per sample (36 B for the unscheduled paper model — exactly the
     seed's `8*4+4`), while the naive path pays
     `(n_transitions + n_observed + 2*n_state) * 4` bytes per sample-DAY.

  2. **Roofline instrumentation** (`roofline_metrics`) — turns a measured
     (simulations, wall clock) cell into `achieved_flops`,
     `achieved_bytes_per_s`, `arithmetic_intensity` and
     `roofline_efficiency` (achieved vs the analytic ceiling
     `min(PEAK_FLOPS, HBM_BW * intensity)`). Every bench-artifact/v1 cell
     carries these fields and `tests/check_bench_regression.py` gates
     efficiency drift, not just wall clock.

  3. **Measured autotuner + persistent cache** (`autotune`, `TuningCache`) —
     a best-of-N search over the knobs that are pure scheduling (and
     therefore stream-invariant):

       * Pallas kernel tile size ({256, 512, 1024, 2048, 4096} filtered to
         divisors of the batch). The kernel's global sample index is
         `idx = lane + tile * tile_idx`, so the RNG stream — and with it the
         accepted particle set — is BIT-IDENTICAL across tiles (pinned by
         tests); the winner is auto-applied.
       * `xla_fused` scan chunking (`lax.scan(..., unroll=k)`), also
         stream-invariant; auto-applied.
       * wave batch size — measured and recorded as `best_batch` but
         ADVISORY ONLY: changing the batch changes the per-wave sample
         streams and hence the accepted set, so it is never applied behind
         the caller's back.

     Winners persist in a JSON cache under `experiments/tuning/` keyed by
     `(backend, model, days, batch, summary, distance, schedule-shape)`.
     `abc.make_simulator` consults the cache at simulator-build time when
     `ABCConfig.autotune` is set (a hit skips all measurement), so campaigns
     and scaling studies pick tuned sizes automatically.

CLI (the nightly cache-refresh job):

    PYTHONPATH=src python -m repro.core.tuning \
        --dataset synthetic_small --models siard sir \
        --backends pallas xla_fused --batch 8192 --days 20
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import time
from pathlib import Path
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# accelerator ceilings (TPU v5e class) shared with benchmarks/roofline.py
from repro.ioutils import atomic_write
from repro.launch.analysis import HBM_BW, PEAK_FLOPS

_REPO = Path(__file__).resolve().parents[3]
#: where tuning winners persist (committed / uploaded by the nightly job)
TUNING_DIR = _REPO / "experiments" / "tuning"
DEFAULT_CACHE_PATH = TUNING_DIR / "cache.json"
CACHE_SCHEMA = "tuning-cache/v1"

#: kernel tile candidates of the measured search (filtered per batch)
TILE_CANDIDATES = (256, 512, 1024, 2048, 4096)
#: lax.scan unroll candidates for the xla_fused running-distance scan
UNROLL_CANDIDATES = (1, 2, 4, 8)
#: wave-batch candidates, as factors of the configured batch (advisory only)
BATCH_FACTORS = (0.5, 1.0, 2.0)


# --------------------------------------------------------------------------
# 1. Analytic cost model, derived from the model spec
# --------------------------------------------------------------------------

#: jaxpr primitives counted as one op per output element. Integer/bitwise ops
#: are included: the counter-based RNG is murmur-style integer mixing and
#: occupies the same VPU issue slots as float math on every target we model.
_OP_PRIMS = frozenset({
    "add", "sub", "mul", "div", "rem", "neg", "sign", "abs",
    "max", "min", "pow", "integer_pow", "sqrt", "rsqrt",
    "log", "log1p", "exp", "expm1", "tanh", "logistic", "erf", "erf_inv",
    "floor", "ceil", "round", "nextafter",
    "sin", "cos", "atan2",
    "eq", "ne", "lt", "le", "gt", "ge",
    "and", "or", "xor", "not",
    "shift_left", "shift_right_logical", "shift_right_arithmetic",
    "select_n", "clamp",
})

#: params keys under which higher-order primitives hide their inner jaxprs
_INNER_JAXPR_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr")


def count_jaxpr_ops(jaxpr) -> float:
    """Arithmetic op count of a (closed) jaxpr, one op per output element.

    Recurses into scan (multiplied by the static trip count), while/cond
    bodies and inlined calls. This is an *operation* count, not an HLO FLOP
    estimate — it is the currency both sides of the cost-model cross-check
    use (tests/test_tuning.py), so only internal consistency matters.
    """
    closed = getattr(jaxpr, "jaxpr", None)
    if closed is not None:  # ClosedJaxpr -> Jaxpr
        jaxpr = closed
    total = 0.0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "scan":
            total += float(eqn.params["length"]) * count_jaxpr_ops(
                eqn.params["jaxpr"]
            )
        elif prim == "while":
            # one iteration of cond+body (trip count is data-dependent)
            total += count_jaxpr_ops(eqn.params["cond_jaxpr"])
            total += count_jaxpr_ops(eqn.params["body_jaxpr"])
        elif prim == "cond":
            total += max(
                (count_jaxpr_ops(b) for b in eqn.params["branches"]),
                default=0.0,
            )
        elif any(k in eqn.params and eqn.params[k] is not None
                 for k in _INNER_JAXPR_KEYS):
            for k in _INNER_JAXPR_KEYS:
                inner = eqn.params.get(k)
                if inner is not None:
                    total += count_jaxpr_ops(inner)
        elif prim in _OP_PRIMS:
            total += float(max(
                (int(np.prod(v.aval.shape)) for v in eqn.outvars), default=1
            ))
    return total


def count_fn_ops(fn, *args) -> float:
    """`count_jaxpr_ops` of `jax.make_jaxpr(fn)(*args)`."""
    return count_jaxpr_ops(jax.make_jaxpr(fn)(*args))


@functools.lru_cache(maxsize=None)
def _flops_per_sample_day(model, schedule, summary, distance: str) -> float:
    """Trace ONE day of the oracle dynamics and count ops per sample.

    All arguments are hashable statics (the model spec is frozen); the day
    index, seed and observed values are traced so nothing constant-folds.
    """
    from repro.core.summaries import (
        get_distance_kind,
        get_summary,
        pool_channels,
        pool_factor,
        running_day,
    )
    from repro.epi import engine
    from repro.kernels import ref

    spec = get_summary(summary)
    kind = get_distance_kind(distance)
    b = 256  # large enough to amortize the few per-day scalar ops
    pool = pool_factor(spec, model.n_regions)
    n_obs = model.total_observed // pool  # summary channels after pooling
    obs_idx = model.total_observed_idx
    width = model.n_params
    if schedule is not None and not schedule.is_empty:
        width += schedule.shape(model).n_scales

    def day(theta, state, cum, binv, acc, day_idx, obs_t, flush_t, seed, idx):
        z = ref.hash_normals(
            seed, idx, day_idx, model.total_transitions, model.ctr_slots
        )
        th_d = engine.effective_theta(model, schedule, theta, day_idx)
        nxt = engine.tau_leap_step(model, state, th_d, z, 1e6)
        cum, binv, acc = running_day(
            spec, kind, jnp.ones((n_obs,), jnp.float32),
            pool_channels(nxt[..., obs_idx], pool), obs_t, flush_t, cum,
            binv, acc,
        )
        return nxt, cum, binv, acc

    args = (
        jnp.zeros((b, width), jnp.float32),          # theta
        jnp.zeros((b, model.total_state), jnp.float32),  # state (all regions)
        jnp.zeros((b, n_obs), jnp.float32),          # cum carry
        jnp.zeros((b, n_obs), jnp.float32),          # bin carry
        jnp.zeros((b,), jnp.float32),                # distance accumulator
        jnp.uint32(0),                               # day index (traced)
        jnp.zeros((n_obs,), jnp.float32),            # observed summary at day
        jnp.float32(1.0),                            # flush flag
        jnp.uint32(0),                               # RNG seed
        jnp.arange(b, dtype=jnp.uint32),             # global sample indices
    )
    return count_fn_ops(day, *args) / b


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Analytic per-sample cost of the fused ABC hot path for one spec."""

    model: str
    days: int
    theta_width: int  # params + schedule scale columns
    #: region-major flattened totals (== the per-region counts at R=1)
    n_transitions: int
    n_state: int
    n_observed: int
    #: traced op count of one simulated day per sample (spec-derived)
    flops_per_sample_day: float
    #: fused-path HBM bytes per sample: theta row in + one distance out
    fused_bytes_per_sample: float
    #: naive-path bytes per sample-DAY: noise + trajectory + state round trip
    naive_bytes_per_sample_day: float
    n_regions: int = 1

    def flops(self, n_samples: float, days: Optional[int] = None) -> float:
        return n_samples * (days or self.days) * self.flops_per_sample_day

    def fused_bytes(self, n_samples: float) -> float:
        return n_samples * self.fused_bytes_per_sample

    def naive_bytes(self, n_samples: float, days: Optional[int] = None) -> float:
        return n_samples * (days or self.days) * self.naive_bytes_per_sample_day

    @property
    def arithmetic_intensity_fused(self) -> float:
        return self.days * self.flops_per_sample_day / self.fused_bytes_per_sample

    @property
    def arithmetic_intensity_naive(self) -> float:
        return self.flops_per_sample_day / self.naive_bytes_per_sample_day


def cost_model(
    model,
    days: int,
    schedule=None,
    summary=None,
    distance: str = "euclidean",
) -> CostModel:
    """Build the analytic cost model for any registered (or ad-hoc) spec.

    `model` is a registry name or a `CompartmentalModel`; `schedule` widens
    theta (more fused bytes) and adds the per-day window selects; `summary`
    and `distance` change the per-day accumulator ops.
    """
    from repro.epi.models import get_model

    spec = get_model(model)
    sched = None
    if schedule is not None and not schedule.is_empty:
        sched = schedule.shape(spec)
    width = spec.n_params + (sched.n_scales if sched is not None else 0)
    f = _flops_per_sample_day(spec, schedule, summary, distance)
    return CostModel(
        model=spec.name,
        days=int(days),
        theta_width=width,
        n_transitions=spec.total_transitions,
        n_state=spec.total_state,
        n_observed=spec.total_observed,
        n_regions=spec.n_regions,
        flops_per_sample_day=f,
        fused_bytes_per_sample=(width + 1) * 4.0,
        naive_bytes_per_sample_day=(
            (spec.total_transitions + spec.total_observed
             + 2 * spec.total_state) * 4.0
        ),
    )


# --------------------------------------------------------------------------
# 2. Roofline instrumentation of measured cells
# --------------------------------------------------------------------------

def roofline_from_totals(flops: float, hbm_bytes: float, wall_s: float) -> Dict:
    """achieved/intensity/efficiency fields from raw totals.

    `roofline_efficiency` is measured throughput over the analytic ceiling
    `min(PEAK_FLOPS, HBM_BW * intensity)` — the number the regression gate
    tracks for drift. On CPU hosts the absolute value is tiny (the ceiling
    models the accelerator); the gate only ever compares it to ITS baseline
    on the same machine class, so relative drift is still meaningful.
    """
    wall_s = max(float(wall_s), 1e-12)
    ai = flops / max(hbm_bytes, 1.0)
    achieved = flops / wall_s
    ceiling = min(PEAK_FLOPS, HBM_BW * ai)
    return {
        "achieved_flops": achieved,
        "achieved_bytes_per_s": hbm_bytes / wall_s,
        "arithmetic_intensity": ai,
        "roofline_efficiency": achieved / max(ceiling, 1e-12),
    }


def roofline_metrics(
    cm: CostModel, n_samples: float, wall_s: float, days: Optional[int] = None
) -> Dict:
    """Instrument one measured cell (simulations, wall clock) -> envelope
    fields. Uses the FUSED byte model — the hot path every backend aspires
    to; the naive/fused intensity comparison lives in benchmarks/roofline.py.
    """
    return roofline_from_totals(
        cm.flops(n_samples, days), cm.fused_bytes(n_samples), wall_s
    )


def bench_cell_metrics(
    model,
    days: int,
    simulations: float,
    wall_s: float,
    schedule=None,
    summary=None,
    distance: str = "euclidean",
) -> Dict:
    """One-call helper for benchmark scripts: cost model + roofline fields."""
    cm = cost_model(model, days, schedule=schedule, summary=summary,
                    distance=distance)
    return roofline_metrics(cm, simulations, wall_s)


# --------------------------------------------------------------------------
# 3. Persistent tuning cache
# --------------------------------------------------------------------------

def _schedule_shape_tag(model, schedule) -> str:
    if schedule is None or schedule.is_empty:
        return "nosched"
    from repro.epi.models import get_model

    shape = schedule.shape(get_model(model))
    return f"w{shape.n_windows}tv{len(shape.tv_indices)}"


def cache_key(
    *,
    backend: str,
    model: str,
    days: int,
    batch: int,
    summary: str = "identity",
    distance: str = "euclidean",
    schedule=None,
    device_kind: Optional[str] = None,
) -> str:
    """The tuning-cache key: everything that changes the tuned optimum,
    the device first (`device_kind=None` reads `jax.devices()[0]`), so a
    winner measured on one device kind is a miss on another."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    sched = _schedule_shape_tag(model, schedule)
    return (
        f"{device_kind}/{backend}/{model}/d{days}/b{batch}/{summary}/"
        f"{distance}/{sched}"
    )


def cfg_cache_key(cfg) -> str:
    """Cache key of an `ABCConfig` (its summary resolved to a stable tag)."""
    return cache_key(
        backend=cfg.backend,
        model=cfg.model,
        days=cfg.num_days,
        batch=cfg.batch_size,
        summary=cfg.summary_spec.tag(),
        distance=cfg.distance,
        schedule=cfg.schedule,
    )


class TuningCache:
    """JSON-backed map of cache_key -> winning knob entry.

    Reads are lazy; writes are atomic (temp file + rename, like ABCState).
    A corrupt or schema-mismatched file raises ValueError LOUDLY instead of
    silently retuning from scratch — a half-written cache hiding a tuned
    winner would quietly cost every nightly run its measurement budget.
    """

    def __init__(self, path: Optional[os.PathLike] = None):
        self.path = Path(path) if path is not None else DEFAULT_CACHE_PATH
        self._entries: Optional[Dict[str, Dict]] = None

    def _load(self) -> None:
        if self._entries is not None:
            return
        if not self.path.exists():
            self._entries = {}
            return
        try:
            payload = json.loads(self.path.read_text())
        except (OSError, json.JSONDecodeError) as e:
            raise ValueError(
                f"corrupt tuning cache {self.path} ({e}); delete it and "
                "re-run autotuning (python -m repro.core.tuning)"
            ) from e
        if (
            not isinstance(payload, dict)
            or payload.get("schema") != CACHE_SCHEMA
            or not isinstance(payload.get("entries"), dict)
        ):
            raise ValueError(
                f"tuning cache {self.path} is not a {CACHE_SCHEMA} payload; "
                "delete it and re-run autotuning (python -m repro.core.tuning)"
            )
        self._entries = payload["entries"]

    def get(self, key: str) -> Optional[Dict]:
        self._load()
        return self._entries.get(key)

    def entries(self) -> Dict[str, Dict]:
        self._load()
        return dict(self._entries)

    def put(self, key: str, entry: Dict) -> None:
        self._load()
        self._entries[key] = entry
        payload = {"schema": CACHE_SCHEMA, "entries": self._entries}
        with atomic_write(self.path, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)


# --------------------------------------------------------------------------
# 4. Measured best-of-N search
# --------------------------------------------------------------------------

def measure_simulator(
    dataset,
    cfg,
    *,
    reps: int = 2,
    warmup: int = 1,
    key: int = 0,
    batch: Optional[int] = None,
) -> float:
    """Best-of-`reps` wall seconds of one simulator batch under `cfg`.

    Builds the backend simulator with autotuning OFF (so the search never
    recurses into itself) and times `simulator(theta, key)` end to end,
    compile/warmup excluded.
    """
    from repro.core.abc import make_simulator
    from repro.core.priors import schedule_prior
    from repro.epi.models import get_model

    b = int(batch or cfg.batch_size)
    cfg = dataclasses.replace(cfg, autotune=False)
    if batch is not None:
        # batch candidates only probe throughput; let the tile auto-resolve
        cfg = dataclasses.replace(cfg, batch_size=b, chunk_size=b, tile=None)
    sim = jax.jit(make_simulator(dataset, cfg))
    prior = schedule_prior(get_model(cfg.model), cfg.schedule)
    theta = prior.sample(jax.random.PRNGKey(key), (b,))
    k_sim = jax.random.PRNGKey(key + 1)
    for _ in range(max(0, warmup)):
        jax.block_until_ready(sim(theta, k_sim))
    best = None
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(sim(theta, k_sim))
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best


def tile_candidates(batch: int) -> Tuple[int, ...]:
    """Search space for the Pallas tile: the fixed candidate grid filtered to
    exact divisors of the batch, plus the legacy auto default."""
    from repro.kernels.ops import resolve_tile

    cands = {t for t in TILE_CANDIDATES if batch % t == 0 and t <= batch}
    auto = resolve_tile(batch, None)
    if batch % auto == 0:
        # the auto default only joins the EXPLICIT candidate set when it
        # divides the batch (explicit tiles never ghost-pad, by contract)
        cands.add(auto)
    return tuple(sorted(cands))


def autotune(
    dataset,
    cfg,
    *,
    cache: Optional[TuningCache] = None,
    reps: int = 2,
    measure: Optional[Callable] = None,
    measure_batches: bool = True,
    verbose: bool = False,
) -> Dict:
    """Measured best-of-N search for `cfg`'s backend; returns the cache entry.

    A cache HIT returns immediately without measuring anything (pinned by
    tests/test_tuning.py). On a miss the search measures, per backend:

      pallas    — every compatible kernel tile (`tile_candidates`); the
                  winner is auto-applied by `resolve_tuned` because tiling
                  is stream-invariant (bit-identical accepted sets).
      xla_fused — the day scan's unroll factor; also stream-invariant.
      (all)     — optionally, wave-batch candidates; `best_batch` is
                  recorded ADVISORY ONLY because the batch size changes the
                  per-wave RNG streams and therefore the accepted set.

    `measure(cfg, batch=None) -> seconds` can be injected for tests.
    """
    cache = cache if cache is not None else TuningCache()
    key = cfg_cache_key(cfg)
    hit = cache.get(key)
    if hit is not None:
        return hit
    if measure is None:
        def measure(c, batch=None):  # noqa: E731 — default measured probe
            return measure_simulator(dataset, c, reps=reps, batch=batch)

    entry: Dict = {
        "schema": CACHE_SCHEMA,
        "device_kind": jax.devices()[0].device_kind,
        "backend": cfg.backend,
        "model": cfg.model,
        "days": cfg.num_days,
        "batch": cfg.batch_size,
        "summary": cfg.summary_spec.tag(),
        "distance": cfg.distance,
        "schedule": _schedule_shape_tag(cfg.model, cfg.schedule),
    }
    measurements: Dict[str, float] = {}

    if cfg.backend == "pallas":
        cands = tile_candidates(cfg.batch_size)
        for t in cands:
            dt = measure(dataclasses.replace(cfg, tile=int(t)))
            measurements[f"tile{t}"] = dt
            if verbose:
                print(f"[tuning] {key}: tile={t} -> {dt * 1e3:.1f} ms")
        if cands:
            best = min(measurements, key=measurements.get)
            entry["tile"] = int(best[len("tile"):])
    elif cfg.backend == "xla_fused":
        for u in UNROLL_CANDIDATES:
            dt = measure(dataclasses.replace(cfg, scan_unroll=int(u)))
            measurements[f"unroll{u}"] = dt
            if verbose:
                print(f"[tuning] {key}: unroll={u} -> {dt * 1e3:.1f} ms")
        best = min(measurements, key=measurements.get)
        entry["scan_unroll"] = int(best[len("unroll"):])

    if measure_batches:
        best_batch, best_tp = None, -1.0
        for f in BATCH_FACTORS:
            b = int(cfg.batch_size * f)
            if b < 256:
                continue
            dt = measure(cfg, batch=b)
            measurements[f"batch{b}"] = dt
            if b / dt > best_tp:
                best_batch, best_tp = b, b / dt
            if verbose:
                print(f"[tuning] {key}: batch={b} -> {b / dt:,.0f} sims/s")
        # advisory: applying it would change the per-wave sample streams
        entry["best_batch"] = best_batch

    entry["measurements"] = measurements
    cache.put(key, entry)
    return entry


def resolve_tuned(dataset, cfg, cache: Optional[TuningCache] = None):
    """An `ABCConfig` with tuned knobs filled in from the cache.

    No-op unless `cfg.autotune` is set. Explicit user settings always win
    over cached winners; `best_batch` is never applied (advisory only). The
    returned config has `autotune=False` so downstream builders — including
    the search's own measurement probes — never re-enter the tuner.
    """
    if not getattr(cfg, "autotune", False):
        return cfg
    entry = autotune(dataset, cfg, cache=cache)
    repl: Dict = {"autotune": False}
    if cfg.tile is None and entry.get("tile"):
        repl["tile"] = int(entry["tile"])
    if cfg.scan_unroll is None and entry.get("scan_unroll"):
        repl["scan_unroll"] = int(entry["scan_unroll"])
    return dataclasses.replace(cfg, **repl)


# --------------------------------------------------------------------------
# CLI: build/refresh the tuning cache (the nightly job's entry point)
# --------------------------------------------------------------------------

def main(argv=None) -> int:
    import argparse

    from repro.core.abc import ABCConfig

    ap = argparse.ArgumentParser(
        description="Measure and persist ABC hot-path tuning winners."
    )
    ap.add_argument("--dataset", default="synthetic_small")
    ap.add_argument("--models", nargs="+", default=["siard", "sir"])
    ap.add_argument("--backends", nargs="+",
                    default=["pallas", "xla_fused"])
    ap.add_argument("--batch", type=int, default=8192)
    ap.add_argument("--days", type=int, default=20)
    ap.add_argument("--summary", default="identity")
    ap.add_argument("--distance", default="euclidean")
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--cache", default=str(DEFAULT_CACHE_PATH))
    ap.add_argument("--no-batch-search", action="store_true",
                    help="skip the (advisory) wave-batch measurements")
    args = ap.parse_args(argv)

    from repro.epi.data import get_dataset

    cache = TuningCache(args.cache)
    for model in args.models:
        ds = get_dataset(args.dataset, num_days=args.days, model=model)
        for backend in args.backends:
            cfg = ABCConfig(
                batch_size=args.batch, chunk_size=args.batch,
                num_days=args.days, backend=backend, model=model,
                summary=None if args.summary == "identity" else args.summary,
                distance=args.distance, autotune=True,
            )
            entry = autotune(ds, cfg, cache=cache, reps=args.reps,
                             measure_batches=not args.no_batch_search,
                             verbose=True)
            knobs = {k: entry.get(k) for k in ("tile", "scan_unroll",
                                               "best_batch")
                     if entry.get(k) is not None}
            print(f"[tuning] {cfg_cache_key(cfg)} -> {knobs}")
            cm = cost_model(model, args.days, summary=cfg.summary,
                            distance=args.distance)
            print(f"[tuning]   cost model: {cm.flops_per_sample_day:.0f} "
                  f"ops/sample-day, {cm.fused_bytes_per_sample:.0f} B/sample "
                  f"fused (AI {cm.arithmetic_intensity_fused:.0f}), "
                  f"{cm.naive_bytes_per_sample_day:.0f} B/sample-day naive")
    print(f"[tuning] cache: {cache.path} ({len(cache.entries())} entries)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
