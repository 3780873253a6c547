"""Capture R=1 golden outputs for the registered models on all backends.

First run against the pre-metapop tree to freeze the exact f32
distances each backend produced before the region-axis refactor; the
committed r1_pins.npz is then asserted bit-identical by
tests/test_metapop.py::test_r1_bit_identity_pins. Re-running this script
against a tree whose R=1 paths changed would regenerate (and silently
launder) the pins — only do that for an intentional, documented stream
change.

Re-captured once on jax 0.9.0, from the tree before the kernel moved its
observed series to SMEM (its one `pl.load` rewritten as the equivalent
`obs_ref[0:n_obs_rows, pl.ds(day, 1)]` read, since jax 0.9.0 has no
`pl.load`): jax 0.9.0 draws a different theta from the same threefry key
than the jax 0.4.37 the first capture ran on, and XLA's CPU log/cos differ
in the last bit, so the old pins failed at their input check.

Usage: PYTHONPATH=src python tests/data/capture_r1_pins.py
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.summaries import get_summary, lower_summary, summary_distance
from repro.epi import engine
from repro.epi.data import get_dataset
from repro.epi.models import get_model, list_models
from repro.kernels import ops, ref

BATCH = 16
DAYS = 14
SEED = 123  # hash-RNG seed (pallas + oracle)
KEY = 5  # threefry key (xla paths)
PIN_MODELS = ("seiard", "seir", "siard", "sir")


def main() -> None:
    out = {}
    for name in PIN_MODELS:
        assert name in list_models(), name
        spec = get_model(name)
        ds = get_dataset("synthetic_small", num_days=DAYS, model=spec)
        cfg = ds.model_config()
        theta = spec.prior().sample(jax.random.PRNGKey(0), (BATCH,))
        obs = jnp.asarray(ds.observed, jnp.float32)
        common = dict(
            population=cfg.population, a0=cfg.a0, r0=cfg.r0, d0=cfg.d0
        )
        key = jax.random.PRNGKey(KEY)

        # pallas kernel (interpret on CPU) + its hash-RNG oracle
        out[f"{name}/pallas"] = np.asarray(
            ops.abc_sim_distance(theta, np.uint32(SEED), obs, model=spec, **common)
        )
        out[f"{name}/oracle"] = np.asarray(
            ref.abc_sim_distance_ref(theta, np.uint32(SEED), obs, model=spec, **common)
        )
        # fused scan (threefry)
        d_fused, _ = engine.simulate_observed_lowmem(spec, theta, key, cfg, obs)
        out[f"{name}/xla_fused"] = np.asarray(d_fused)
        # post-hoc xla (threefry, same stream as fused)
        sim = engine.simulate_observed(spec, theta, key, cfg)
        lowered = lower_summary(get_summary(None), "euclidean", obs)
        out[f"{name}/xla"] = np.asarray(
            summary_distance("euclidean", lowered, sim)
        )
        out[f"{name}/theta"] = np.asarray(theta)
        out[f"{name}/observed"] = np.asarray(obs)

    path = os.path.join(os.path.dirname(__file__), "r1_pins.npz")
    np.savez(path, **out)
    print(f"wrote {path}:")
    for k in sorted(out):
        v = out[k]
        print(f"  {k}: shape={v.shape} first={v.ravel()[0]:.6f}")


if __name__ == "__main__":
    main()
