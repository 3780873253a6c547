"""Plain reference of one ABC fit, independent of the program under test.

The program draws its randomness with `jax.random` at stated keys; the
reference draws the same numbers at the same keys and recomputes
everything else from the model's equations (`configs/<reference>`):

  wave w of a fit with key k, shard d of `shards`:
      kw = fold_in(k, w), then fold_in(kw, d) when shards > 1
      k_prior, k_sim = split(kw)
      theta = low + uniform(k_prior, [B, p]) * (high - low)
  day t of a simulation:
      z  = normal(fold_in(k_sim, t), [B, R * T])   (slot r*T + k)
      n  = floor(h + sqrt(h) * z), clamped to what each source still holds
  distance = sqrt(sum over days and observed channels of (x - y)^2)
  tolerance pilot wave w:  theta ~ fold_in(fold_in(pilot, w), 0),
                           simulate with fold_in(fold_in(pilot, w), 1)

`dtype` selects the precision: float32 is the reference, bfloat16 is the
control that the comparison must reject. Given several `devices`, a batch
is split over them by rows; the draws do not change with the split
(partitionable threefry, JAX's default).
"""

from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

CONFIGS = Path(__file__).resolve().parent / "configs"


def load_model(config: dict):
    """The configuration's plain model module, `configs/<reference>`."""
    path = CONFIGS / config["reference"]
    spec = importlib.util.spec_from_file_location(
        f"bench_ref_{config['name']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def mobility_matrix(config: dict) -> np.ndarray:
    """[R, R] row-stochastic coupling from the configuration's grammar."""
    n = int(config["regions"])
    text = config.get("mobility")
    if not text:
        return np.eye(n, dtype=np.float32)
    kind, _, arg = text.partition(":")
    if kind != "ring":
        raise ValueError(f"unsupported mobility {text!r}")
    eps = float(arg)
    m = np.zeros((n, n), np.float64)
    for r in range(n):
        m[r, r] = 1.0 - eps
        m[r, (r - 1) % n] += eps / 2.0
        m[r, (r + 1) % n] += eps / 2.0
    return m.astype(np.float32)


class Reference:
    """Distances of a whole batch for one configuration and dataset."""

    def __init__(self, config: dict, dataset, dtype=jnp.float32,
                 devices=None):
        self.config = config
        self.model = load_model(config)
        self.dtype = dtype
        self.days = int(config["num_days"])
        self.regions = int(config["regions"])
        self.low = jnp.asarray(config["prior_low"], jnp.float32)
        self.high = jnp.asarray(config["prior_high"], jnp.float32)
        self.data = (
            jnp.asarray(np.asarray(dataset.observed)[:, : self.days],
                        jnp.float32),
            jnp.float32(dataset.population), jnp.float32(dataset.a0),
            jnp.float32(dataset.r0), jnp.float32(dataset.d0),
            jnp.asarray(mobility_matrix(config)),
        )
        self.row_sharding = None
        if devices is not None and len(devices) > 1:
            mesh = jax.sharding.Mesh(np.asarray(devices), ("rows",))
            self.row_sharding = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("rows"))

    def batch(self, key, batch: int, pilot: bool = False):
        """(theta [B, p] f32, distance [B] f32) of one batch keyed `key`."""
        return _batch(self.model, self.dtype, self.days, self.regions,
                      batch, pilot, self.row_sharding, key, self.low,
                      self.high, *self.data)

    def wave(self, fit_key, w: int, batch: int, shards: int):
        """Per shard (theta, distance) of wave `w` of the fit keyed `fit_key`."""
        kw = jax.random.fold_in(fit_key, w)
        if shards == 1:
            return [self.batch(kw, batch)]
        return [self.batch(jax.random.fold_in(kw, d), batch)
                for d in range(shards)]

    def pilot_epsilon(self, key, quantile: float, n_pilot: int,
                      batch: int) -> float:
        """The tolerance at `quantile` of the pilot's finite distances."""
        per_wave = min(n_pilot, batch)
        dists = []
        for w in range(max(1, n_pilot // per_wave)):
            _, d = self.batch(jax.random.fold_in(key, w), per_wave, pilot=True)
            d = np.asarray(d)
            dists.append(d[np.isfinite(d)])
        return float(np.quantile(np.concatenate(dists), quantile))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _batch(model, dtype, days, regions, batch, pilot, row_sharding, key,
           low, high, observed, population, a0, r0, d0, mobility):
    def shard_rows(x):
        if row_sharding is None:
            return x
        return jax.lax.with_sharding_constraint(x, row_sharding)

    if pilot:
        k_prior, k_sim = jax.random.fold_in(key, 0), jax.random.fold_in(key, 1)
    else:
        k_prior, k_sim = jax.random.split(key)
    u = shard_rows(jax.random.uniform(k_prior, (batch, low.shape[0]),
                                      jnp.float32))
    theta = low + u * (high - low)
    th = theta.astype(dtype)
    pop, a0, r0, d0 = (x.astype(dtype) for x in (population, a0, r0, d0))
    mob = mobility.astype(dtype)
    n_t = len(model.TRANSITIONS)
    rows = [jnp.asarray(x, dtype) for x in
            model.initial(th, pop, a0, r0, d0, regions, dtype)]
    obs = observed.astype(dtype)

    def day(carry, t):
        rows, acc = carry
        z = shard_rows(jax.random.normal(jax.random.fold_in(k_sim, t),
                                         (batch, regions * n_t), jnp.float32))
        z = z.reshape(batch, regions, n_t).astype(dtype)
        h = [jnp.maximum(x, 0.0) for x in
             model.hazards(rows, th, pop, mob, dtype)]
        raw = [jnp.floor(h[k] + jnp.sqrt(h[k]) * z[..., k])
               for k in range(n_t)]
        left = {}
        counts = []
        for k, (src, _dst) in enumerate(model.TRANSITIONS):
            avail = left.get(src, rows[src])
            n_k = jnp.clip(raw[k], 0.0, avail)
            left[src] = avail - n_k
            counts.append(n_k)
        rows = list(rows)
        for k, (src, dst) in enumerate(model.TRANSITIONS):
            rows[src] = rows[src] - counts[k]
            rows[dst] = rows[dst] + counts[k]
        # region-major observed channels: slot r * n_obs + m
        x = jnp.stack([rows[c] for c in model.OBSERVED], axis=-1)
        x = x.reshape(batch, regions * len(model.OBSERVED))
        diff = x - jax.lax.dynamic_index_in_dim(obs, t, axis=1,
                                                keepdims=False)
        return (rows, acc + jnp.sum(diff * diff, axis=-1)), None

    acc0 = jnp.zeros((batch,), dtype)
    (_, acc), _ = jax.lax.scan(day, (rows, acc0), jnp.arange(days))
    dist = jnp.sqrt(acc).astype(jnp.float32)
    return theta, shard_rows(jnp.where(jnp.isnan(dist), jnp.inf, dist))


def control_fit(ref: Reference, fit_key, epsilon: float, target: int,
                batch: int, shards: int, max_waves: int):
    """The reference put in the program's place: waves until `target` rows
    lie within `epsilon`. Returns (theta [n, p], distance [n], waves) with
    the rows in shard-then-wave order, as the program's buffers hold them."""
    kept = [[] for _ in range(shards)]
    n, w = 0, 0
    while n < target and w < max_waves:
        for s, (theta, dist) in enumerate(ref.wave(fit_key, w, batch, shards)):
            theta, dist = np.asarray(theta), np.asarray(dist)
            m = dist <= epsilon
            kept[s].append((theta[m], dist[m]))
            n += int(m.sum())
        w += 1
    rows = [r for shard in kept for r in shard]
    return (np.concatenate([t for t, _ in rows]),
            np.concatenate([d for _, d in rows]), w)
