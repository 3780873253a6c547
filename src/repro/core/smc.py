"""SMC-ABC: sequential Monte Carlo ABC with a decreasing tolerance schedule.

The paper (§2.2) notes that instead of a fixed threshold, SMC can transform an
initial sample set into a high-quality set with a decreasing sequence of
tolerances [Drovandi & Pettitt 2011; Warne et al. 2020]. This is the batched
ABC-PMC variant (Beaumont-style): every proposal wave is a full vectorized
batch, so the engine reuses the paper's parallel simulate->distance machinery.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.abc import ABCConfig, compact_accepted, make_simulator, run_param_names
from repro.core.posterior import Posterior
from repro.core.priors import UniformBoxPrior, schedule_prior
from repro.epi.data import CountryData
from repro.epi.models import get_model
from repro.epi.spec import InterventionSchedule


@dataclasses.dataclass(frozen=True)
class SMCConfig:
    n_particles: int = 256
    batch_size: int = 4096  # proposals per wave
    n_rounds: int = 4
    quantile: float = 0.5  # eps_{t+1} = this quantile of current distances
    kernel_scale: float = 2.0  # Beaumont: perturbation var = scale * weighted var
    num_days: int = 49
    backend: str = "xla_fused"
    max_waves_per_round: int = 200
    min_tolerance: float = 0.0
    #: compartmental model to infer: a registry name or a CompartmentalModel
    #: spec object (ad-hoc regionalized metapop specs work unregistered)
    model: object = "siard"
    #: metapop models only: row-stochastic [R, R] mobility override (nested
    #: tuples), forwarded to the simulator (see ABCConfig.mobility)
    mobility: Optional[Tuple[Tuple[float, ...], ...]] = None
    #: distance kind over summary values (core.summaries.DISTANCE_KINDS)
    distance: str = "euclidean"
    #: summary statistic (SummarySpec / registry name / None = raw daily);
    #: lowered by every backend exactly as in rejection ABC
    summary: Optional[object] = None
    #: optional intervention schedule; particles widen with per-window scale
    #: columns (pinned zero-width scale dims are never perturbed)
    schedule: Optional[InterventionSchedule] = None
    #: Pallas dispatch override for backend="pallas" (see ABCConfig.interpret)
    interpret: Optional[bool] = None
    #: "host": numpy proposal loop with one device sync per wave (original
    #: structure). "device": each round's propose -> simulate -> accept loop
    #: is a single jitted lax.while_loop that fills the particle buffer
    #: on-device and syncs once per round. Streams differ (jax vs numpy RNG)
    #: but both are seeded and deterministic; statistical behaviour is pinned
    #: by tests/test_posterior_recovery.py.
    wave_loop: str = "host"
    #: optional warm start: seed round 0 from this particle set [N, p]
    #: (e.g. yesterday's cached posterior) instead of a fresh prior wave.
    #: The set is resampled by `initial_weights` (uniform when None) to
    #: exactly n_particles and re-simulated against the CURRENT dataset,
    #: so round 0 costs n_particles simulations instead of batch_size —
    #: the serving layer's daily re-fit path (repro.core.serving).
    initial_particles: Optional[object] = None
    #: importance weights of `initial_particles` (None = uniform)
    initial_weights: Optional[object] = None

    def __post_init__(self):
        if self.wave_loop not in ("host", "device"):
            raise ValueError(f"unknown wave_loop {self.wave_loop!r}")
        if self.initial_weights is not None and self.initial_particles is None:
            raise ValueError("initial_weights given without initial_particles")
        if self.initial_particles is not None:
            init = np.asarray(self.initial_particles, np.float32)
            if init.ndim != 2 or init.shape[0] == 0:
                raise ValueError(
                    f"initial_particles must be a non-empty [N, p] array, "
                    f"got shape {init.shape}"
                )
            if self.initial_weights is not None:
                w = np.asarray(self.initial_weights, np.float64)
                if w.shape != (init.shape[0],):
                    raise ValueError(
                        f"initial_weights shape {w.shape} does not match "
                        f"{init.shape[0]} initial particles"
                    )
                if (w < 0).any() or not np.isfinite(w).all() or w.sum() <= 0:
                    raise ValueError(
                        "initial_weights must be finite, non-negative and "
                        "sum to a positive value"
                    )


def _weighted_var(theta: np.ndarray, w: np.ndarray) -> np.ndarray:
    mu = np.average(theta, axis=0, weights=w)
    return np.average((theta - mu) ** 2, axis=0, weights=w) + 1e-12


def make_smc_round_fn(simulator, prior: UniformBoxPrior, cfg: SMCConfig):
    """Device-resident SMC proposal round (the SMC face of the ABC device
    wave loop): a jitted lax.while_loop that resamples parents by weight,
    perturbs, simulates and compacts acceptances into a fixed particle
    buffer until `n_particles` proposals are accepted or the wave budget is
    spent. Proposal semantics match the host loop (first-accepted-first, out
    of bounds / NaN rejected); only the RNG stream differs (threefry here).

    round_fn(key, particles [n,p], log_weights [n], sigma [p], eps,
             max_waves) -> (theta_buf, dist_buf, n_accepted, waves_done)
    """
    B, n_p = cfg.batch_size, cfg.n_particles
    lo = jnp.asarray(prior.lows, jnp.float32)
    hi = jnp.asarray(prior.highs, jnp.float32)
    cap = n_p + B  # a final wave's overshoot always fits

    def round_fn(key, particles, log_weights, sigma, eps, max_waves):
        p = particles.shape[1]

        def cond(carry):
            w, n, *_ = carry
            return jnp.logical_and(n < n_p, w < max_waves)

        def body(carry):
            w, n, th_buf, d_buf = carry
            k = jax.random.fold_in(key, w)
            k_par, k_pert, k_sim = jax.random.split(k, 3)
            parents = jax.random.categorical(k_par, log_weights, shape=(B,))
            prop = particles[parents] + sigma * jax.random.normal(
                k_pert, (B, p), jnp.float32
            )
            inside = jnp.all((prop >= lo) & (prop <= hi), axis=-1)
            d = simulator(prop, k_sim)
            d = jnp.where(jnp.isnan(d) | ~inside, jnp.inf, d)
            th_buf, d_buf, n = compact_accepted(
                th_buf, d_buf, n, prop, d, d <= eps, cap
            )
            return (w + 1, n, th_buf, d_buf)

        th0 = jnp.zeros((cap, p), jnp.float32)
        d0 = jnp.full((cap,), jnp.inf, jnp.float32)
        w, n, th_buf, d_buf = jax.lax.while_loop(
            cond, body, (jnp.int32(0), jnp.int32(0), th0, d0)
        )
        return th_buf, d_buf, n, w

    return jax.jit(round_fn)


def make_sharded_smc_round_fn(mesh, simulator, prior: UniformBoxPrior,
                              cfg: SMCConfig):
    """Multi-device SMC proposal round under the scaling study's sharding.

    Each device of the mesh proposes `batch_size / n_dev` particles per wave
    from the REPLICATED parent population (resampling and perturbation stay
    device-resident between waves, keyed by `fold_in(fold_in(key, w), dev)`),
    simulates its own sub-batch and compacts acceptances into its own buffer
    segment; the only steady-state collective is the per-wave psum of the
    scalar accept count feeding the shared stop condition — the exact
    property that bounds the ABC wave loop's scaling overhead.

    round_fn(key, particles [n,p], log_weights [n], sigma [p], eps,
             max_waves) -> (theta_buf [n_dev*cap, p], dist_buf, n_accepted,
                            waves_done, fills [n_dev])

    The sample stream differs from the single-device round (per-device key
    folds), but is deterministic in (key, mesh shape); statistical behaviour
    matches the host/device rounds (tests/test_scaling.py).
    """
    from jax.sharding import PartitionSpec as P

    from repro.core.abc import compact_accepted as _compact
    from repro.core.distributed import data_axes

    axes = data_axes(mesh)
    n_dev = 1
    for a in axes:
        n_dev *= mesh.shape[a]
    if cfg.batch_size % n_dev:
        raise ValueError(
            f"batch_size {cfg.batch_size} not divisible by {n_dev} devices"
        )
    B, n_p = cfg.batch_size // n_dev, cfg.n_particles
    lo = jnp.asarray(prior.lows, jnp.float32)
    hi = jnp.asarray(prior.highs, jnp.float32)
    cap = n_p + B  # a final wave's overshoot always fits per shard

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P()),
        out_specs=(P(axes), P(axes), P(), P(), P(axes)),
    )
    def round_fn(key, particles, log_weights, sigma, eps, max_waves):
        dev = jax.lax.axis_index(axes)
        p = particles.shape[1]

        def cond(carry):
            w, n, *_ = carry
            return jnp.logical_and(n < n_p, w < max_waves)

        def body(carry):
            w, n, fill, th_buf, d_buf = carry
            k = jax.random.fold_in(jax.random.fold_in(key, w), dev)
            k_par, k_pert, k_sim = jax.random.split(k, 3)
            parents = jax.random.categorical(k_par, log_weights, shape=(B,))
            prop = particles[parents] + sigma * jax.random.normal(
                k_pert, (B, p), jnp.float32
            )
            inside = jnp.all((prop >= lo) & (prop <= hi), axis=-1)
            d = simulator(prop, k_sim)
            d = jnp.where(jnp.isnan(d) | ~inside, jnp.inf, d)
            th_buf, d_buf, new_fill = _compact(
                th_buf, d_buf, fill, prop, d, d <= eps, cap
            )
            # the total of the cumulative fills, not a running sum of
            # increments (see abc.wave_loop_body: XLA would move that
            # all-reduce out of the loop, past the stop test)
            n = jax.lax.psum(new_fill, axes)
            return (w + 1, n, new_fill, th_buf, d_buf)

        # the per-shard fill and buffers vary across the mesh; the wave and
        # global accept counters stay replicated (psum'd)
        fill0, th0, d0 = jax.lax.pcast(
            (
                jnp.int32(0),
                jnp.zeros((cap, p), jnp.float32),
                jnp.full((cap,), jnp.inf, jnp.float32),
            ),
            axes,
            to="varying",
        )
        w, n, fill, th_buf, d_buf = jax.lax.while_loop(
            cond, body, (jnp.int32(0), jnp.int32(0), fill0, th0, d0),
        )
        return th_buf, d_buf, n, w, jnp.minimum(fill, cap)[None]

    return jax.jit(round_fn)


def run_smc_abc(
    dataset: CountryData,
    cfg: SMCConfig,
    key: jax.Array | int = 0,
    prior: Optional[UniformBoxPrior] = None,
    verbose: bool = False,
    mesh=None,
) -> Posterior:
    """Returns the final particle population as a Posterior.

    With `mesh` (and `cfg.wave_loop == "device"`), each round's
    propose/simulate/accept loop is sharded across the mesh's devices with
    per-shard buffers and a psum'd stop condition
    (`make_sharded_smc_round_fn`) — the SMC face of the scaling study."""
    spec = get_model(cfg.model)
    if isinstance(key, int):
        key = jax.random.PRNGKey(key)
    prior = prior or schedule_prior(spec, cfg.schedule)
    abc_cfg = ABCConfig(
        batch_size=cfg.batch_size,
        tolerance=np.inf,
        target_accepted=cfg.n_particles,
        strategy="topk",
        top_k=cfg.batch_size,
        num_days=cfg.num_days,
        backend=cfg.backend,
        model=cfg.model,
        schedule=cfg.schedule,
        interpret=cfg.interpret,
        distance=cfg.distance,
        summary=cfg.summary,
        mobility=cfg.mobility,
    )
    simulator = make_simulator(dataset, abc_cfg)
    sim_jit = jax.jit(simulator)
    round_fn = None
    sharded = mesh is not None
    if sharded and cfg.wave_loop != "device":
        raise ValueError("sharded SMC requires wave_loop='device'")
    if cfg.wave_loop == "device":
        round_fn = (
            make_sharded_smc_round_fn(mesh, simulator, prior, cfg)
            if sharded
            else make_smc_round_fn(simulator, prior, cfg)
        )
    lo = np.asarray(prior.lows, np.float32)
    hi = np.asarray(prior.highs, np.float32)
    # zero-width prior dims are point masses (pinned intervention scales):
    # they get no perturbation noise and stay out of the kernel density
    free = np.asarray(prior.free_dims(), bool)
    t0 = time.time()

    # --- round 0 -----------------------------------------------------------
    k0, key = jax.random.split(key)
    if cfg.initial_particles is not None:
        # warm start: resample the provided population by weight to exactly
        # n_particles and re-simulate it against the CURRENT dataset (the
        # data may have changed since the population was fitted) — round 0
        # costs n_particles simulations instead of a full prior wave
        init = np.asarray(cfg.initial_particles, np.float32)
        if init.shape[1] != lo.shape[0]:
            raise ValueError(
                f"initial_particles have width {init.shape[1]}; model "
                f"{cfg.model!r} with this schedule expects {lo.shape[0]}"
            )
        w0 = (
            np.asarray(cfg.initial_weights, np.float64)
            if cfg.initial_weights is not None
            else np.full(init.shape[0], 1.0)
        )
        w0 = w0 / w0.sum()
        # particles from a stale fit can sit marginally outside a changed
        # prior box; clip so their prior density (and kernel weights) stay
        # finite rather than silently zeroing the whole population
        init = np.clip(init, lo, hi)
        idx = np.asarray(
            jax.random.choice(
                k0, init.shape[0], shape=(cfg.n_particles,), replace=True,
                p=jnp.asarray(w0, jnp.float32),
            )
        )
        particles = init[idx]
        d0 = np.asarray(
            sim_jit(jnp.asarray(particles), jax.random.fold_in(key, 0))
        )
        dists = np.where(np.isnan(d0), np.inf, d0)
        sims = cfg.n_particles
    else:
        # cold start: prior wave, keep the best n_particles
        theta0 = prior.sample(k0, (cfg.batch_size,))
        d0 = np.asarray(sim_jit(theta0, jax.random.fold_in(key, 0)))
        d0 = np.where(np.isnan(d0), np.inf, d0)
        order = np.argsort(d0)[: cfg.n_particles]
        particles = np.asarray(theta0)[order]
        dists = d0[order]
        sims = cfg.batch_size
    weights = np.full(cfg.n_particles, 1.0 / cfg.n_particles)
    finite = dists[np.isfinite(dists)]
    eps = float(np.max(finite)) if finite.size else float("inf")

    rng = np.random.default_rng(np.asarray(jax.random.key_data(key))[-1])
    for rnd in range(1, cfg.n_rounds + 1):
        eps = max(float(np.quantile(dists, cfg.quantile)), cfg.min_tolerance)
        sigma = np.sqrt(cfg.kernel_scale * _weighted_var(particles, weights))
        sigma = np.where(free, sigma, 0.0).astype(np.float32)
        new_theta = np.zeros_like(particles)
        new_dist = np.full(cfg.n_particles, np.inf, np.float32)
        n_done = 0
        if round_fn is not None:
            # device-resident round: the whole propose/simulate/accept loop
            # runs in one jitted while_loop; a single host sync per round
            key, k_round = jax.random.split(key)
            logw = np.log(np.maximum(weights, 1e-38)).astype(np.float32)
            out = round_fn(
                k_round,
                jnp.asarray(particles),
                jnp.asarray(logw),
                jnp.asarray(sigma, jnp.float32),
                np.float32(eps),
                np.int32(cfg.max_waves_per_round),
            )
            if sharded:
                # gather the per-shard buffer segments in shard order (the
                # host re-entry of the sharded round); the global accept
                # count can exceed the kept population, like any overshoot
                th_buf, d_buf, n_acc, waves, fills = out
                th, d = np.asarray(th_buf), np.asarray(d_buf)
                fills = np.asarray(fills)
                cap = th.shape[0] // fills.shape[0]
                seg_th = [th[s * cap: s * cap + int(c)]
                          for s, c in enumerate(fills)]
                seg_d = [d[s * cap: s * cap + int(c)]
                         for s, c in enumerate(fills)]
                acc_th = np.concatenate(seg_th, axis=0)
                acc_d = np.concatenate(seg_d, axis=0)
                n_done = min(acc_th.shape[0], cfg.n_particles)
                sims += int(waves) * cfg.batch_size
                new_theta[:n_done] = acc_th[:n_done]
                new_dist[:n_done] = acc_d[:n_done]
            else:
                th_buf, d_buf, n_acc, waves = out
                n_done = min(int(n_acc), cfg.n_particles)
                sims += int(waves) * cfg.batch_size
                new_theta[:n_done] = np.asarray(th_buf)[:n_done]
                new_dist[:n_done] = np.asarray(d_buf)[:n_done]
        else:
            for wave in range(cfg.max_waves_per_round):
                # propose a full batch: resample parents by weight, perturb
                parents = rng.choice(cfg.n_particles, size=cfg.batch_size, p=weights)
                prop = particles[parents] + rng.normal(
                    0.0, sigma, size=(cfg.batch_size, particles.shape[1])
                ).astype(np.float32)
                inside = np.all((prop >= lo) & (prop <= hi), axis=1)
                key, kw = jax.random.split(key)
                d = np.asarray(sim_jit(jnp.asarray(prop), kw))
                d = np.where(np.isnan(d) | ~inside, np.inf, d)
                sims += cfg.batch_size
                ok = np.nonzero(d <= eps)[0]
                take = ok[: cfg.n_particles - n_done]
                if take.size:
                    sl = slice(n_done, n_done + take.size)
                    new_theta[sl] = prop[take]
                    new_dist[sl] = d[take]
                    n_done += take.size
                if n_done >= cfg.n_particles:
                    break
        if n_done < cfg.n_particles:
            # could not refresh the full population at this tolerance; keep
            # the best of old+new to stay robust (documented fallback)
            n_keep = cfg.n_particles - n_done
            keep = np.argsort(dists)[:n_keep]
            new_theta[n_done:] = particles[keep]
            new_dist[n_done:] = dists[keep]
        # weight update: w_i ∝ prior(theta_i) / sum_j w_j K(theta_i | theta_j)
        # (pinned dims divide by 1 — their diffs are exactly 0 — and are
        # excluded from the kernel normalization)
        denom_sig = np.where(free, sigma, 1.0)
        diff = (new_theta[:, None, :] - particles[None, :, :]) / denom_sig[None, None, :]
        log_k = -0.5 * np.sum(diff * diff, axis=-1)  # [new, old], up to const
        log_k -= np.sum(np.log(sigma[free]))  # kernel normalization (shared const)
        mx = log_k.max(axis=1, keepdims=True)
        denom = (weights[None, :] * np.exp(log_k - mx)).sum(axis=1)
        log_prior = np.asarray(prior.log_pdf(jnp.asarray(new_theta)))
        w = np.exp(log_prior - (np.log(denom) + mx[:, 0]))
        w = np.where(np.isfinite(w), w, 0.0)
        weights = w / w.sum() if w.sum() > 0 else np.full_like(w, 1.0 / len(w))
        particles, dists = new_theta, new_dist
        if verbose:
            print(
                f"[smc] round {rnd}: eps={eps:.4g} mean_dist={dists.mean():.4g} "
                f"ess={1.0 / np.sum(weights ** 2):.1f}"
            )

    return Posterior(
        theta=particles,
        distances=dists,
        tolerance=eps,
        param_names=run_param_names(abc_cfg, spec),
        runs=cfg.n_rounds,
        simulations=sims,
        wall_time_s=time.time() - t0,
        weights=weights,
    )
