"""The comparison that decides `correct`.

Each number below is compared with the cell's limit in
`limits/<cell>.json`; `correct` holds when every number is at or under it.

  eps_gap        widest |eps_program - eps_reference| / eps_reference over the
                 datasets: the tolerance pilot, recomputed by the reference
  dist_gap       widest |d_program - d_reference| / eps over the accepted rows
                 of the sampled fits: the simulator and the distance
  set_diff       rows of the sampled fits accepted on one side only, leaving
                 out rows whose reference distance lies within dist_gap's
                 limit of eps; a program row that the reference never drew,
                 or holds twice, counts too: the wave loop's acceptance and
                 compaction (and on several chips, each shard's stream)
  stop_gap       waves by which a sampled fit stopped before or after the
                 first wave whose cumulative count reaches the target
  short_fits     fits of the window holding fewer rows than the target
  over_eps       accepted rows of the window's fits above their eps
  sims_mismatch  fits whose simulation count is not waves x wave size
"""

from __future__ import annotations

import math

import numpy as np

#: a program row matches a reference row when every parameter agrees to
#: this share of its prior width (the draws are the same f32 numbers)
THETA_MATCH = 1e-6


def window_counts(fits, target: int, wave_size: int) -> dict:
    """The counts that cover every fit of the window."""
    short = over = mismatch = 0
    for f in fits:
        short += len(f.distances) < target
        d = np.asarray(f.distances, np.float64)
        over += int(np.sum(~(d <= f.epsilon)))
        mismatch += f.simulations != f.waves * wave_size
    return {"short_fits": float(short), "over_eps": float(over),
            "sims_mismatch": float(mismatch)}


def _match(theta_p, theta_r, width):
    """For each program row, the index of the reference row it equals to
    THETA_MATCH of the prior width, or -1."""
    order = np.argsort(theta_r[:, 0], kind="stable")
    col0 = theta_r[order, 0]
    tol = THETA_MATCH * width
    out = np.full(len(theta_p), -1, np.int64)
    for i, row in enumerate(theta_p):
        j = int(np.searchsorted(col0, row[0] - tol[0], side="left"))
        best, best_gap = -1, 1.0
        while j < len(col0) and col0[j] <= row[0] + tol[0]:
            gap = float(np.max(np.abs(theta_r[order[j]] - row) / width))
            if gap <= THETA_MATCH and gap < best_gap:
                best, best_gap = int(order[j]), gap
            j += 1
        out[i] = best
    return out


def check_fit(ref, fit, batch: int, shards: int, target: int, band: float,
              max_waves: int) -> dict:
    """dist_gap, set_diff and stop_gap of one fit against the reference.

    `fit` has theta, distances, waves, epsilon and key; `band` is the share
    of eps within which a row's side cannot be judged (dist_gap's limit)."""
    eps = float(fit.epsilon)
    waves = int(fit.waves)
    run = min(waves, max_waves)
    thetas, dists, wave_of = [], [], []
    for w in range(run):
        for theta, dist in ref.wave(fit.key, w, batch, shards):
            thetas.append(np.asarray(theta))
            dists.append(np.asarray(dist))
            wave_of.append(np.full(len(dists[-1]), w, np.int64))
    p = np.asarray(fit.theta, np.float32)
    d_p = np.asarray(fit.distances, np.float64)
    out = {"dist_gap": 0.0, "set_diff": 0.0,
           "stop_gap": float(max(0, waves - max_waves))}
    if not thetas:
        out["set_diff"] = float(len(p))
        out["stop_gap"] = max(out["stop_gap"], 1.0)
        return out
    theta_r = np.concatenate(thetas)
    d_r = np.concatenate(dists).astype(np.float64)
    wave_r = np.concatenate(wave_of)
    width = np.asarray(ref.high - ref.low, np.float64)
    idx = _match(p.astype(np.float64), theta_r.astype(np.float64), width)
    hit = idx >= 0
    set_diff = int(np.sum(~hit))
    matched = idx[hit]
    set_diff += len(matched) - len(np.unique(matched))
    if hit.any():
        gap = np.abs(d_p[hit] - d_r[matched]) / eps
        gap = np.where(np.isfinite(gap), gap, math.inf)
        out["dist_gap"] = float(gap.max())
        set_diff += int(np.sum(d_r[matched] > eps * (1.0 + band)))
    sure = d_r <= eps * (1.0 - band)
    taken = np.zeros(len(d_r), bool)
    taken[matched] = True
    set_diff += int(np.sum(sure & ~taken))
    out["set_diff"] = float(set_diff)
    # the first wave at which the target is certainly (n_lo) or possibly
    # (n_hi) reached, from the rows whose side is or is not in doubt
    maybe = d_r <= eps * (1.0 + band)
    n_lo = np.cumsum(np.bincount(wave_r[sure], minlength=run))
    n_hi = np.cumsum(np.bincount(wave_r[maybe], minlength=run))
    if waves <= max_waves:
        if n_hi[-1] < target:
            out["stop_gap"] = max(out["stop_gap"], 1.0)
        elif run > 1 and n_lo[-2] >= target:
            first = int(np.argmax(n_lo >= target)) + 1
            out["stop_gap"] = max(out["stop_gap"], float(waves - first))
    return out


def worst(readings: list[dict]) -> dict:
    """The largest reading of each number."""
    out: dict = {}
    for r in readings:
        for k, v in r.items():
            out[k] = max(out.get(k, -math.inf), float(v))
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) in the limits' order."""
    table = {}
    ok = True
    for name, limit in limits.items():
        value = float(numbers.get(name, math.inf))
        table[name] = {"value": value, "limit": float(limit)}
        ok &= bool(value <= limit)
    return ok, table
