"""Amortized inference: neural posterior estimation over the tau-leap engine.

The ABC/SMC backends pay ~1e6 simulations PER POSTERIOR FIT; the NPE line of
work (PAPERS.md: the SBI-vs-MCMC comparison, NPE for stochastic epidemic
models, the SBI-methods assessment) converges on the amortized alternative:
train a conditional density estimator q(theta | x) ONCE on simulator output,
then posterior inference for any new observed series is a single forward
pass — no waves, no tolerance schedule. This repo already owned every
ingredient; this module only wires them together:

  * the tau-leap engine is an infinite training-set generator —
    `epi.engine.simulate_features` yields device-resident batches of
    `(theta ~ prior, x = summary(simulate(theta)))` pairs, one jitted call
    per training step, so no dataset is ever materialized on disk;
  * `core.summaries` provides the conditioning features: the SAME flush-day
    summary values the ABC running accumulator compares
    (`summary_features`), so the estimator conditions on exactly the
    statistic the ABC distance sees;
  * the estimator is a small mixture-density network built from
    `models.common` blocks (layer_norm + GELU MLP residual blocks) with a
    K-component diagonal-Gaussian head over box-standardized theta,
    optimized with the repo's own AdamW (`optim.adamw`).

Entry points:

  * `train_npe(dataset, cfg, key)`   — train an `NPEstimator` for an
    `ABCConfig(backend="npe")`; the dataset contributes its scalars
    (population, a0, r0, d0) to the simulator, NOT its observed series —
    the estimator amortizes over observation content.
  * `NPEstimator.sample_posterior(observed, n)` — one forward pass + n
    mixture draws; returns the same `Posterior` object ABC produces
    (`distances` holds the negative log-density of each draw, so
    `Posterior.top(k)` selects the highest-density samples; `tolerance` is
    0.0 — there is no epsilon), so `PosteriorStore`, `serve --epi`,
    forecasting and the campaign consumers work unchanged.
  * `fine_tune(est, dataset, key)`   — a short continuation of training on
    fresh simulations: the serving layer's re-fit path
    (`abc_serve --backend npe`), replacing a full wave campaign with
    `NPEConfig.fine_tune_steps` gradient steps (0 = pure forward pass).
  * `run_npe(dataset, cfg, key)`     — the `run_abc` face: train + sample
    `cfg.target_accepted` draws conditioned on the dataset's observed
    series. `core.abc.run_abc` dispatches here for `backend="npe"`.

Accuracy is validated against the ABC posterior as oracle: on the
tests/test_posterior_recovery.py fixtures the NPE credible intervals must
overlap the ABC intervals and the posterior means must agree within
prior-width bounds. Determinism: training and sampling are threefry-keyed
jitted programs, so a fixed seed reproduces the estimator and its samples
exactly (pinned by tests).
"""

from __future__ import annotations

import dataclasses
import json
import time
import zipfile
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.posterior import Posterior
from repro.core.priors import UniformBoxPrior, schedule_prior
from repro.core.summaries import SummarySpec, get_summary, summary_features
from repro.epi import engine
from repro.epi.data import CountryData
from repro.epi.models import get_model
from repro.epi.spec import InterventionSchedule
from repro.ioutils import atomic_write
from repro.models.common import layer_norm, ninit, vanilla_mlp
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update

Array = jax.Array

#: fold_in salts separating the training / pilot / sampling key streams
_PILOT_SALT = 0x9112
_SAMPLE_SALT = 0x5A3D

#: softplus offset putting the initial component sigma near 0.45 — wide
#: enough to cover the unit box before training shapes it
_SIGMA0 = -0.4328


@dataclasses.dataclass(frozen=True)
class NPEConfig:
    """Training hyperparameters of the NPE backend (`ABCConfig.npe`).

    Defaults are sized for the CI container: a tiny MDN trained on ~1e5
    simulated pairs in seconds. Production fits raise `train_steps` /
    `train_batch` / `hidden`; everything stays device-resident either way.
    """

    #: gradient steps; each step simulates a FRESH `train_batch` of pairs
    train_steps: int = 400
    #: simulations per step (the infinite-training-set generator batch)
    train_batch: int = 256
    #: MLP width of the conditioning trunk
    hidden: int = 64
    #: residual (layer_norm -> GELU MLP) blocks after the input projection
    n_layers: int = 2
    #: mixture components of the diagonal-Gaussian head
    n_components: int = 4
    lr: float = 3e-3
    weight_decay: float = 1e-4
    #: floor on component sigmas (box-standardized units)
    sigma_min: float = 1e-3
    #: prior-predictive simulations used to standardize the features once
    n_pilot: int = 512
    #: gradient steps of a serving re-fit (`fine_tune`); 0 makes a dataset
    #: refresh a pure forward pass
    fine_tune_steps: int = 100
    fine_tune_lr: float = 1e-3

    def __post_init__(self):
        if self.train_steps < 1:
            raise ValueError(f"train_steps must be >= 1, got {self.train_steps}")
        if self.train_batch < 2:
            raise ValueError(f"train_batch must be >= 2, got {self.train_batch}")
        if self.hidden < 1 or self.n_layers < 0 or self.n_components < 1:
            raise ValueError(
                f"invalid MDN shape: hidden={self.hidden} "
                f"n_layers={self.n_layers} n_components={self.n_components}"
            )
        if self.fine_tune_steps < 0:
            raise ValueError(
                f"fine_tune_steps must be >= 0, got {self.fine_tune_steps}"
            )
        if self.sigma_min <= 0:
            raise ValueError(f"sigma_min must be > 0, got {self.sigma_min}")


def resolve_npe_config(npe) -> NPEConfig:
    """None -> defaults; validates the type loudly."""
    if npe is None:
        return NPEConfig()
    if not isinstance(npe, NPEConfig):
        raise TypeError(
            f"cfg.npe must be an NPEConfig or None, got {type(npe).__name__}"
        )
    return npe


# ----------------------------------------------------------------- MDN core
def mdn_init(key, n_features: int, n_params: int, cfg: NPEConfig) -> dict:
    """Initialize the mixture-density network parameters (f32 pytree).

    Trunk: input projection -> `n_layers` residual blocks (layer_norm +
    GELU MLP, `models.common` building blocks). Head: one linear layer to
    K * (1 + 2p) raw outputs (logits, means, sigma pre-activations). The
    head bias spreads the K component means across the unit box so the
    mixture starts diverse instead of collapsed.
    """
    K, p, H = cfg.n_components, n_params, cfg.hidden
    ks = jax.random.split(key, 2 + cfg.n_layers)
    f32 = jnp.float32
    blocks = []
    for i in range(cfg.n_layers):
        k1, k2 = jax.random.split(ks[2 + i])
        blocks.append({
            "ln_s": jnp.ones((H,), f32),
            "ln_b": jnp.zeros((H,), f32),
            "w1": ninit(k1, (H, 2 * H), dtype=f32),
            "b1": jnp.zeros((2 * H,), f32),
            "w2": ninit(k2, (2 * H, H), fan_in=2 * H, dtype=f32),
            "b2": jnp.zeros((H,), f32),
        })
    head_b = np.zeros((K * (1 + 2 * p),), np.float32)
    # component k's mean starts at (k + 0.5) / K on every standardized dim
    head_b[K : K + K * p] = np.repeat(
        (np.arange(K) + 0.5) / K - 0.5, p
    ).astype(np.float32)
    return {
        "in_w": ninit(ks[0], (n_features, H), dtype=f32),
        "in_b": jnp.zeros((H,), f32),
        "blocks": tuple(blocks),
        "head_w": ninit(ks[1], (H, K * (1 + 2 * p)), fan_in=H, dtype=f32),
        "head_b": jnp.asarray(head_b),
    }


def mdn_forward(
    params: dict, x: Array, cfg: NPEConfig, n_params: int
) -> Tuple[Array, Array, Array]:
    """x [..., F] -> (log_pi [..., K], mu [..., K, p], sigma [..., K, p]).

    mu is offset to the box center (0.5) and sigma floors at
    `cfg.sigma_min`, so an untrained net already emits a proper density
    over the standardized box.
    """
    K, p = cfg.n_components, n_params
    h = jax.nn.gelu((x @ params["in_w"] + params["in_b"]).astype(jnp.float32))
    for blk in params["blocks"]:
        h = h + vanilla_mlp(
            layer_norm(h, blk["ln_s"], blk["ln_b"]),
            blk["w1"], blk["b1"], blk["w2"], blk["b2"],
        )
    out = h @ params["head_w"] + params["head_b"]
    log_pi = jax.nn.log_softmax(out[..., :K], axis=-1)
    mu = 0.5 + out[..., K : K + K * p].reshape(out.shape[:-1] + (K, p))
    raw = out[..., K + K * p :].reshape(out.shape[:-1] + (K, p))
    sigma = cfg.sigma_min + jax.nn.softplus(raw + _SIGMA0)
    return log_pi, mu, sigma


def mdn_log_prob(
    params: dict, x: Array, theta_std: Array, cfg: NPEConfig, n_params: int
) -> Array:
    """Mixture log-density of box-standardized theta given features x.

    x [..., F], theta_std [..., p] -> [...]; the mixture is over K diagonal
    Gaussians, reduced with a logsumexp over components.
    """
    log_pi, mu, sigma = mdn_forward(params, x, cfg, n_params)
    t = theta_std[..., None, :]  # [..., 1, p]
    z = (t - mu) / sigma
    comp = -0.5 * jnp.sum(z * z, axis=-1) - jnp.sum(
        jnp.log(sigma), axis=-1
    ) - 0.5 * n_params * jnp.log(2.0 * jnp.pi)
    return jax.nn.logsumexp(log_pi + comp, axis=-1)


def mdn_sample(
    params: dict, x: Array, key: Array, n: int, cfg: NPEConfig, n_params: int
) -> Array:
    """Draw n standardized samples from q(theta | x) for ONE feature vector.

    x [F] -> theta_std [n, p]: categorical over components, then the
    component's diagonal Gaussian.
    """
    log_pi, mu, sigma = mdn_forward(params, x, cfg, n_params)
    k_c, k_n = jax.random.split(key)
    comp = jax.random.categorical(k_c, log_pi, shape=(n,))  # [n]
    eps = jax.random.normal(k_n, (n, n_params), jnp.float32)
    return mu[comp] + sigma[comp] * eps


# ------------------------------------------------------------- the estimator
@dataclasses.dataclass
class NPEstimator:
    """A trained amortized posterior q(theta | summary features).

    Tied to (model, num_days, summary, schedule, dataset scalars) — NOT to
    the observed series content: any new observation of the same shape is a
    forward pass. `sample_posterior` returns the standard `Posterior`
    container, so every downstream consumer (store, server, forecasts,
    campaign reports) is oblivious to how the samples were produced.
    """

    model: str
    num_days: int
    summary: SummarySpec
    schedule: Optional[InterventionSchedule]
    npe: NPEConfig
    param_names: Tuple[str, ...]
    lows: np.ndarray  # [p] prior box (widened for the schedule)
    highs: np.ndarray  # [p]
    feat_mean: np.ndarray  # [F] pilot standardization
    feat_std: np.ndarray  # [F]
    params: dict  # MDN pytree
    train_steps_done: int = 0
    train_sims: int = 0
    train_wall_s: float = 0.0
    final_loss: float = float("nan")

    @property
    def n_params(self) -> int:
        return int(self.lows.shape[0])

    @property
    def n_features(self) -> int:
        return int(self.feat_mean.shape[0])

    def _widths(self) -> np.ndarray:
        # zero-width (pinned) dims train/sample at a constant 0 in
        # standardized space; the clamp only guards the division
        return np.maximum(self.highs - self.lows, 1e-6)

    def features_of(self, observed) -> np.ndarray:
        """Observed series [n_obs, T>=num_days] -> standardized features [F]."""
        obs = np.asarray(observed, np.float32)[:, : self.num_days]
        if obs.shape[-1] < self.num_days:
            raise ValueError(
                f"observed series has {obs.shape[-1]} days; this estimator "
                f"conditions on {self.num_days}"
            )
        spec = get_model(self.model)
        x = np.asarray(summary_features(self.summary, obs, spec.n_regions))
        if x.shape != self.feat_mean.shape:
            raise ValueError(
                f"observed summary has {x.shape[0]} features; estimator was "
                f"trained on {self.n_features} (wrong channels or summary?)"
            )
        return (x - self.feat_mean) / self.feat_std

    def sample_posterior(self, observed, n: int, key: Array | int = 0) -> Posterior:
        """n posterior draws conditioned on an observed series — one forward
        pass, zero simulations.

        Returns a `Posterior` whose `distances` hold each draw's NEGATIVE
        log-density under the estimator (so `top(k)` picks the densest
        samples), `tolerance` 0.0, and `simulations` the cumulative TRAINING
        cost — the amortized denominator, unchanged by queries.
        """
        t0 = time.time()
        if isinstance(key, int):
            key = jax.random.PRNGKey(key)
        key = jax.random.fold_in(key, _SAMPLE_SALT)
        x = jnp.asarray(self.features_of(observed))
        t_std = mdn_sample(self.params, x, key, int(n), self.npe, self.n_params)
        t_std = jnp.clip(t_std, 0.0, 1.0)
        nlq = -mdn_log_prob(self.params, x, t_std, self.npe, self.n_params)
        theta = np.asarray(t_std) * self._widths() + self.lows
        theta = np.clip(theta, self.lows, self.highs)
        return Posterior(
            theta=theta,
            distances=np.asarray(nlq, np.float32),
            tolerance=0.0,
            param_names=self.param_names,
            runs=0,
            simulations=self.train_sims,
            wall_time_s=time.time() - t0,
        )

    def log_prob(self, observed, theta) -> np.ndarray:
        """Standardized-space log q(theta | observed) per row of theta [N, p]."""
        x = jnp.asarray(self.features_of(observed))
        t_std = (np.asarray(theta, np.float32) - self.lows) / self._widths()
        return np.asarray(
            mdn_log_prob(self.params, x, jnp.asarray(t_std), self.npe,
                         self.n_params)
        )

    # -- persistence -------------------------------------------------------
    def save(self, path: str) -> None:
        """Atomic .npz save (shared `repro.ioutils.atomic_write` semantics:
        a crash mid-write never leaves a truncated estimator where the
        serving layer reads). Params are stored as canonically-flattened
        leaves; the structure is rebuilt from the config at load."""
        meta = {
            "model": self.model,
            "num_days": self.num_days,
            "summary": dataclasses.asdict(self.summary),
            "schedule": None if self.schedule is None
            else dataclasses.asdict(self.schedule),
            "npe": dataclasses.asdict(self.npe),
            "param_names": list(self.param_names),
            "train_steps_done": int(self.train_steps_done),
            "train_sims": int(self.train_sims),
            "train_wall_s": float(self.train_wall_s),
            "final_loss": float(self.final_loss)
            if np.isfinite(self.final_loss) else None,
        }
        leaves = jax.tree.leaves(self.params)
        arrays = {
            "meta": np.asarray(json.dumps(meta)),
            "lows": self.lows, "highs": self.highs,
            "feat_mean": self.feat_mean, "feat_std": self.feat_std,
        }
        for i, leaf in enumerate(leaves):
            arrays[f"leaf_{i:03d}"] = np.asarray(leaf, np.float32)
        with atomic_write(path, "wb") as f:
            np.savez(f, **arrays)

    @staticmethod
    def load(path: str) -> "NPEstimator":
        """Load a saved estimator; corrupt/truncated files raise ValueError
        with a remediation hint (the Posterior.load contract); a missing
        file propagates FileNotFoundError untouched."""
        try:
            z = np.load(path, allow_pickle=False)
            meta = json.loads(str(z["meta"]))
            npe_cfg = NPEConfig(**meta["npe"])
            summary = SummarySpec(**{
                k: tuple(v) if isinstance(v, list) else v
                for k, v in meta["summary"].items()
            })
            sched = meta["schedule"]
            if sched is not None:
                sched = InterventionSchedule(
                    tv_params=tuple(sched["tv_params"]),
                    breakpoints=tuple(sched["breakpoints"]),
                    scale_lows=tuple(map(tuple, sched["scale_lows"])),
                    scale_highs=tuple(map(tuple, sched["scale_highs"])),
                )
            lows = np.asarray(z["lows"], np.float32)
            feat_mean = np.asarray(z["feat_mean"], np.float32)
            template = mdn_init(
                jax.random.PRNGKey(0), feat_mean.shape[0], lows.shape[0],
                npe_cfg,
            )
            treedef = jax.tree.structure(template)
            n_leaves = treedef.num_leaves
            leaves = [
                jnp.asarray(z[f"leaf_{i:03d}"], jnp.float32)
                for i in range(n_leaves)
            ]
            t_leaves = jax.tree.leaves(template)
            for got, want in zip(leaves, t_leaves):
                if got.shape != want.shape:
                    raise ValueError(
                        f"leaf shape {got.shape} != expected {want.shape}"
                    )
            est = NPEstimator(
                model=str(meta["model"]),
                num_days=int(meta["num_days"]),
                summary=summary,
                schedule=sched,
                npe=npe_cfg,
                param_names=tuple(meta["param_names"]),
                lows=lows,
                highs=np.asarray(z["highs"], np.float32),
                feat_mean=feat_mean,
                feat_std=np.asarray(z["feat_std"], np.float32),
                params=jax.tree.unflatten(treedef, leaves),
                train_steps_done=int(meta["train_steps_done"]),
                train_sims=int(meta["train_sims"]),
                train_wall_s=float(meta["train_wall_s"]),
                final_loss=float("nan") if meta["final_loss"] is None
                else float(meta["final_loss"]),
            )
        except FileNotFoundError:
            raise
        except (zipfile.BadZipFile, OSError, KeyError, ValueError,
                TypeError, json.JSONDecodeError) as e:
            raise ValueError(
                f"corrupt or incomplete NPE estimator file {path!r} ({e}); "
                "it was probably truncated by an interrupted save — delete "
                "it to re-train from scratch"
            ) from e
        return est


# ------------------------------------------------------------------ training
def _train_setup(dataset: CountryData, cfg, prior: Optional[UniformBoxPrior]):
    """Shared resolution for train_npe / fine_tune: (spec, prior, mcfg,
    mobility, summary, npe_cfg). Validates dataset/model compatibility the
    way make_simulator does."""
    from repro.core.abc import resolved_mobility

    spec = get_model(cfg.model)
    if not dataset.compatible_with(spec):
        raise ValueError(
            f"dataset {dataset.name!r} holds {dataset.model!r} series; model "
            f"{spec.name!r} observes different channels"
        )
    prior = prior or schedule_prior(spec, cfg.schedule)
    mcfg = dataset.model_config(cfg.num_days)
    mob = resolved_mobility(cfg, spec, dataset)
    return spec, prior, mcfg, mob, cfg.summary_spec, resolve_npe_config(cfg.npe)


def _make_train_step(spec, prior, mcfg, schedule, summary, mobility,
                     npe_cfg: NPEConfig, opt_cfg: AdamWConfig,
                     lows, highs, feat_mean, feat_std):
    """One jitted training step: simulate a fresh batch of pairs, take one
    AdamW step on the MDN negative log-likelihood."""
    # analysis: allow(scalar-closure-capture) — n_params sizes the MDN head
    # reshape (shape-determining, MUST be a compile constant), and the step
    # is built once per estimator whose parameter count never changes
    n_params = int(lows.shape[0])
    lo = jnp.asarray(lows, jnp.float32)
    width = jnp.asarray(np.maximum(highs - lows, 1e-6), jnp.float32)
    mu_x = jnp.asarray(feat_mean, jnp.float32)
    sd_x = jnp.asarray(feat_std, jnp.float32)

    def loss_fn(params, theta, feats):
        x = (feats - mu_x) / sd_x
        t_std = (theta - lo) / width
        return -jnp.mean(
            mdn_log_prob(params, x, t_std, npe_cfg, n_params)
        )

    @jax.jit
    def step(params, opt_state, key):
        k_prior, k_sim = jax.random.split(key)
        theta = prior.sample(k_prior, (npe_cfg.train_batch,))
        feats = engine.simulate_features(
            spec, theta, k_sim, mcfg, schedule, None, summary, mobility
        )
        loss, grads = jax.value_and_grad(loss_fn)(params, theta, feats)
        params, opt_state, _ = adamw_update(params, grads, opt_state, opt_cfg)
        return params, opt_state, loss

    return step


def _pilot_stats(spec, prior, mcfg, schedule, summary, mobility,
                 npe_cfg: NPEConfig, key):
    """Feature standardization from one prior-predictive pilot batch.

    Computed ONCE at training time and frozen into the estimator —
    fine-tuning continues under the same normalization, so the trained
    trunk weights stay valid."""
    k1, k2 = jax.random.split(jax.random.fold_in(key, _PILOT_SALT))
    theta = prior.sample(k1, (npe_cfg.n_pilot,))
    feats = np.asarray(engine.simulate_features(
        spec, theta, k2, mcfg, schedule, None, summary, mobility
    ))
    mean = feats.mean(axis=0).astype(np.float32)
    std = np.maximum(feats.std(axis=0), 1e-3).astype(np.float32)
    return mean, std


def train_npe(
    dataset: CountryData,
    cfg,
    key: Array | int = 0,
    prior: Optional[UniformBoxPrior] = None,
    verbose: bool = False,
) -> NPEstimator:
    """Train an amortized posterior for `ABCConfig(backend="npe")`.

    Every training step simulates a FRESH `npe.train_batch` of
    (theta, features) pairs inside the jitted step — the engine as an
    infinite training-set generator. Total simulation cost:
    `n_pilot + train_steps * train_batch`, paid once; afterwards every
    posterior query is a forward pass.
    """
    t0 = time.time()
    if isinstance(key, int):
        key = jax.random.PRNGKey(key)
    spec, prior, mcfg, mob, summary, npe_cfg = _train_setup(
        dataset, cfg, prior
    )
    schedule = cfg.schedule
    lows = np.asarray(prior.lows, np.float32)
    highs = np.asarray(prior.highs, np.float32)
    feat_mean, feat_std = _pilot_stats(
        spec, prior, mcfg, schedule, summary, mob, npe_cfg, key
    )
    params = mdn_init(
        jax.random.fold_in(key, 0), feat_mean.shape[0], lows.shape[0], npe_cfg
    )
    opt_cfg = AdamWConfig(
        lr=npe_cfg.lr, weight_decay=npe_cfg.weight_decay,
        warmup_steps=max(1, npe_cfg.train_steps // 20),
        total_steps=npe_cfg.train_steps,
    )
    step = _make_train_step(
        spec, prior, mcfg, schedule, summary, mob, npe_cfg, opt_cfg,
        lows, highs, feat_mean, feat_std,
    )
    opt_state = adamw_init(params)
    loss = None
    for i in range(npe_cfg.train_steps):
        params, opt_state, loss = step(
            params, opt_state, jax.random.fold_in(key, i + 1)
        )
        if verbose and (i + 1) % 100 == 0:
            print(f"[npe] step {i + 1}/{npe_cfg.train_steps}: "
                  f"nll {float(loss):.3f}")
    from repro.core.abc import run_param_names

    return NPEstimator(
        model=spec.name,
        num_days=cfg.num_days,
        summary=summary,
        schedule=schedule,
        npe=npe_cfg,
        param_names=tuple(run_param_names(cfg, spec)),
        lows=lows,
        highs=highs,
        feat_mean=feat_mean,
        feat_std=feat_std,
        params=params,
        train_steps_done=npe_cfg.train_steps,
        train_sims=npe_cfg.n_pilot
        + npe_cfg.train_steps * npe_cfg.train_batch,
        train_wall_s=time.time() - t0,
        final_loss=float(loss) if loss is not None else float("nan"),
    )


def fine_tune(
    est: NPEstimator,
    dataset: CountryData,
    key: Array | int = 0,
    steps: Optional[int] = None,
    verbose: bool = False,
) -> NPEstimator:
    """Continue training an estimator for a few steps on fresh simulations.

    The serving re-fit path: when a dataset's content version moves, the
    stored estimator needs no full re-train — the posterior conditions on
    the NEW observed features at query time — but a short fine-tune keeps
    the density head sharp against simulator drift (e.g. updated dataset
    scalars). `steps` defaults to `est.npe.fine_tune_steps`; 0 returns the
    estimator unchanged (a pure forward-pass refresh). Feature
    standardization and the prior box are frozen from the original
    training.
    """
    steps = est.npe.fine_tune_steps if steps is None else int(steps)
    if steps == 0:
        return est
    t0 = time.time()
    if isinstance(key, int):
        key = jax.random.PRNGKey(key)
    spec = get_model(est.model)
    if not dataset.compatible_with(spec):
        raise ValueError(
            f"dataset {dataset.name!r} holds {dataset.model!r} series; "
            f"estimator was trained for {est.model!r}"
        )
    prior = UniformBoxPrior(highs=tuple(est.highs), lows=tuple(est.lows))
    mcfg = dataset.model_config(est.num_days)
    from repro.core.abc import resolved_mobility

    # a geography's matrix, as in training on this dataset
    mob = resolved_mobility(None, spec, dataset)
    opt_cfg = AdamWConfig(
        lr=est.npe.fine_tune_lr, weight_decay=est.npe.weight_decay,
        warmup_steps=1, total_steps=max(steps, 1),
    )
    step_fn = _make_train_step(
        spec, prior, mcfg, est.schedule, est.summary, mob, est.npe, opt_cfg,
        est.lows, est.highs, est.feat_mean, est.feat_std,
    )
    params, opt_state, loss = est.params, adamw_init(est.params), None
    for i in range(steps):
        params, opt_state, loss = step_fn(
            params, opt_state, jax.random.fold_in(key, i + 1)
        )
    if verbose:
        print(f"[npe] fine-tuned {steps} steps: nll {float(loss):.3f}")
    return dataclasses.replace(
        est,
        params=params,
        train_steps_done=est.train_steps_done + steps,
        train_sims=est.train_sims + steps * est.npe.train_batch,
        train_wall_s=est.train_wall_s + (time.time() - t0),
        final_loss=float(loss) if loss is not None else est.final_loss,
    )


def run_npe(
    dataset: CountryData,
    cfg,
    key: Array | int = 0,
    prior: Optional[UniformBoxPrior] = None,
    verbose: bool = False,
) -> Posterior:
    """The `run_abc` face of the NPE backend: train, then sample
    `cfg.target_accepted` posterior draws conditioned on the dataset's
    observed series. `core.abc.run_abc` dispatches here for
    `ABCConfig(backend="npe")`; the returned `Posterior` carries the
    training simulations in `simulations` (the amortized cost) and the
    total wall time including training."""
    t0 = time.time()
    if isinstance(key, int):
        key = jax.random.PRNGKey(key)
    est = train_npe(dataset, cfg, key, prior=prior, verbose=verbose)
    post = est.sample_posterior(
        dataset.observed[:, : cfg.num_days], cfg.target_accepted, key=key
    )
    post.wall_time_s = time.time() - t0
    return post
