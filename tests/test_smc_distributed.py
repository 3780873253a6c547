"""SMC-ABC quality tests + multi-device shard_map driver tests (subprocess)."""

import numpy as np
import pytest

from conftest import run_in_subprocess


def test_smc_abc_tightens_posterior():
    """SMC-ABC must reach a lower tolerance than a single prior wave and keep
    a full particle population."""
    import jax
    from repro.core.smc import SMCConfig, run_smc_abc
    from repro.epi.data import get_dataset

    ds = get_dataset("synthetic_small", num_days=15)
    cfg = SMCConfig(
        n_particles=64, batch_size=2048, n_rounds=3, quantile=0.5, num_days=15
    )
    post = run_smc_abc(ds, cfg, key=0)
    assert len(post) == 64
    # tolerance after 3 halvings of the population quantile must be far below
    # the prior-predictive median distance
    from repro.core.abc import ABCConfig, make_simulator
    from repro.core.priors import paper_prior

    sim = jax.jit(make_simulator(ds, ABCConfig(num_days=15, backend="xla_fused")))
    th = paper_prior().sample(jax.random.PRNGKey(1), (2048,))
    d_prior = np.asarray(sim(th, jax.random.PRNGKey(2)))
    d_prior = d_prior[np.isfinite(d_prior)]
    assert post.tolerance < np.quantile(d_prior, 0.08)
    assert np.isfinite(post.distances).all()
    # posterior mean closer to truth than prior mean (normalized)
    true = np.asarray(ds.true_theta)
    highs = np.asarray(paper_prior().highs)
    err_post = np.abs(post.theta.mean(axis=0) - true) / highs
    err_prior = np.abs(highs / 2 - true) / highs
    assert err_post.mean() < err_prior.mean()


@pytest.mark.slow
def test_shardmap_runner_multi_device():
    """Explicit per-device ABC replica on 8 host devices: global accept count
    must equal the host-side filter count, and the sample stream must be
    deterministic in (key, device)."""
    out = run_in_subprocess(
        """
import jax, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.abc import ABCConfig, make_simulator
from repro.core.distributed import make_shardmap_runner, make_pjit_runner
from repro.core.priors import paper_prior
from repro.epi.data import get_dataset
from repro.launch.mesh import make_auto_mesh

assert len(jax.devices()) == 8
mesh = make_auto_mesh((8,), ("data",))
ds = get_dataset("synthetic_small", num_days=15)
cfg = ABCConfig(batch_size=8 * 512, tolerance=1.6e4, target_accepted=10**9,
                chunk_size=128, strategy="outfeed", num_days=15,
                backend="xla_fused", max_runs=1)
sim = make_simulator(ds, cfg)
runner = make_shardmap_runner(mesh, paper_prior(), sim, cfg)
key = jax.random.PRNGKey(0)
out = runner(key)
d = np.asarray(out.dist)          # [global_chunks, chunk]
flags = np.asarray(out.chunk_flags)
count = int(out.accept_count)
assert d.shape == (8 * 512 // 128, 128), d.shape
host_count = int((d <= cfg.tolerance).sum())
assert count == host_count, (count, host_count)
np.testing.assert_array_equal(flags, (d <= cfg.tolerance).any(axis=1))
# determinism
out2 = runner(key)
np.testing.assert_array_equal(np.asarray(out2.dist), d)
# pjit runner gives a valid stream too
runner_p = make_pjit_runner(mesh, paper_prior(), sim, cfg)
outp = runner_p(key)
dp = np.asarray(outp.dist)
assert int(outp.accept_count) == int((dp <= cfg.tolerance).sum())
print("OK", count)
""",
        n_devices=8,
    )
    assert "OK" in out


@pytest.mark.slow
def test_scaling_device_counts_same_statistics():
    """Paper claim C5 scaffold: accept-rate is device-count independent."""
    rates = {}
    for n in (1, 4):
        out = run_in_subprocess(
            f"""
import jax, numpy as np
from repro.core.abc import ABCConfig, make_simulator
from repro.core.distributed import make_shardmap_runner
from repro.core.priors import paper_prior
from repro.epi.data import get_dataset
from repro.launch.mesh import make_auto_mesh
mesh = make_auto_mesh(({n},), ("data",))
ds = get_dataset("synthetic_small", num_days=15)
cfg = ABCConfig(batch_size={n} * 2048, tolerance=1.8e4, target_accepted=10**9,
                chunk_size=256, num_days=15, backend="xla_fused", max_runs=1)
runner = make_shardmap_runner(mesh, paper_prior(), make_simulator(ds, cfg), cfg)
total = 0
for r in range(4):
    out = runner(jax.random.fold_in(jax.random.PRNGKey(1), r))
    total += int(out.accept_count)
print("RATE", total / (4 * cfg.batch_size))
""",
            n_devices=n,
        )
        rates[n] = float(out.split("RATE")[1].strip())
    assert rates[1] > 0
    assert abs(rates[1] - rates[4]) / rates[1] < 0.8, rates
