"""core/distributed.py edge cases that only bite on real multi-device
meshes: uneven batch splits, lopsided per-shard accept buffers, chunk flags
on a partially accepting final wave, and the 1-device-mesh degenerate."""

import jax
import numpy as np

from conftest import run_in_subprocess
from repro.core.abc import ABCConfig, ABCState, run_abc
from repro.epi.data import get_dataset

DAYS = 12


def test_uneven_batch_per_device_split_raises():
    """A global batch that does not divide the device count must be refused
    loudly by every sharded runner — a silent floor-div would change the
    sample stream and the simulation budget."""
    out = run_in_subprocess(
        f"""
import jax
from repro.core.abc import ABCConfig, make_simulator, make_parametric_simulator
from repro.core import distributed
from repro.core.scaling import device_mesh
from repro.epi.data import get_dataset
from repro.epi.models import get_model

mesh = device_mesh(4)
ds = get_dataset("synthetic_small", num_days={DAYS})
cfg = ABCConfig(batch_size=1023, tolerance=1.6e4, chunk_size=1023,
                num_days={DAYS}, wave_loop="device")
prior = get_model(cfg.model).prior()
sim = make_simulator(ds, cfg)
spec = get_model(cfg.model)
for maker in (distributed.make_shardmap_runner,
              distributed.make_shardmap_wave_runner):
    try:
        maker(mesh, prior, sim, cfg)
    except ValueError as e:
        assert "not divisible" in str(e), e
    else:
        raise AssertionError(f"{{maker.__name__}} accepted an uneven split")
try:
    distributed.make_shardmap_scenario_runner(
        mesh, prior, make_parametric_simulator(spec, cfg), cfg)
except ValueError as e:
    assert "not divisible" in str(e), e
else:
    raise AssertionError("scenario runner accepted an uneven split")
print("OK")
""",
        n_devices=4,
    )
    assert "OK" in out


def test_one_shard_overflows_while_others_stay_empty():
    """A lopsided accept pattern (only shard 0's region of parameter space
    accepts) must clamp that shard's fill to its capacity, leave the other
    segments untouched, and still count every acceptance globally."""
    out = run_in_subprocess(
        """
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.core.abc import ABCConfig, build_wave_loop, WaveLoopOutput
from repro.core.scaling import device_mesh
from repro.epi.models import get_model

n_dev, local_b = 4, 64
cfg = ABCConfig(batch_size=n_dev * local_b, tolerance=1.0,
                target_accepted=10**6, chunk_size=n_dev * local_b,
                max_runs=3, num_days=10, wave_loop="device")
prior = get_model("siard").prior()
mesh = device_mesh(n_dev)
cap = local_b  # deliberately small: one all-accept wave fills it exactly

def sim(theta, key, _data):
    # only shard 0 accepts anything, ever
    dev = jax.lax.axis_index("data")
    return jnp.where(dev == 0, 0.0, jnp.inf) * jnp.ones((theta.shape[0],))

loop = build_wave_loop(
    prior, sim, cfg, batch_size=local_b, capacity=cap,
    fold_axis=lambda: jax.lax.axis_index("data"),
    count_all=lambda c: jax.lax.psum(c, "data"),
)

@partial(jax.shard_map, mesh=mesh,
         in_specs=(P(), P(), P("data"), P("data"), P(), P("data"), P(), P(),
                   P()),
         out_specs=WaveLoopOutput(P("data"), P("data"), P(), P(), P("data")))
def sharded(key, run_idx0, th, d, n0, fills, max_waves, tol, data):
    return loop(key, run_idx0, th, d, n0, fills[0], max_waves, tol, data)

th0 = jnp.zeros((n_dev * cap, prior.dim), jnp.float32)
d0 = jnp.full((n_dev * cap,), jnp.inf, jnp.float32)
out = sharded(jax.random.PRNGKey(0), jnp.int32(0), th0, d0, jnp.int32(0),
              jnp.zeros((n_dev,), jnp.int32), jnp.int32(3), jnp.float32(1.0),
              jnp.zeros((), jnp.int32))
fills = np.asarray(out.fill_counts)
np.testing.assert_array_equal(fills, [cap, 0, 0, 0])
assert int(out.waves_done) == 3
assert int(out.n_accepted) == 3 * local_b  # every acceptance counted
d = np.asarray(out.dist_buf)
assert np.isfinite(d[:cap]).all()          # shard 0: clamped but full
assert np.isinf(d[cap:]).all()             # other segments untouched
print("OK")
""",
        n_devices=4,
    )
    assert "OK" in out


def test_effective_chunk_flags_on_partial_final_wave():
    """On a wave that accepts into only SOME chunks (the partially filled
    final wave of a run), the sharded runner's chunk flags must mark exactly
    the chunks holding accepts, and harvesting flagged chunks must recover
    every accepted sample."""
    out = run_in_subprocess(
        f"""
import jax, numpy as np
from repro.core.abc import ABCConfig, make_simulator
from repro.core.distributed import effective_chunk_flags, make_shardmap_runner
from repro.core.scaling import device_mesh
from repro.epi.data import get_dataset
from repro.epi.models import get_model

mesh = device_mesh(4)
ds = get_dataset("synthetic_small", num_days={DAYS})
# epsilon tight enough that most 128-sample chunks are empty: the partially
# accepting wave the outfeed path exists for
cfg = ABCConfig(batch_size=4 * 1024, tolerance=2.7e3, target_accepted=10**9,
                chunk_size=128, num_days={DAYS}, max_runs=1)
prior = get_model(cfg.model).prior()
runner = make_shardmap_runner(mesh, prior, make_simulator(ds, cfg), cfg)
out = runner(jax.random.PRNGKey(5))
d = np.asarray(out.dist)
flags = np.asarray(effective_chunk_flags(out))
expected = (d <= cfg.tolerance).any(axis=1)
np.testing.assert_array_equal(flags, expected)
assert 0 < flags.sum() < flags.size, flags.sum()  # partial, not degenerate
# harvesting only flagged chunks recovers every accepted sample
n_flagged = sum(int((d[ci] <= cfg.tolerance).sum())
                for ci in np.nonzero(flags)[0])
assert n_flagged == int(out.accept_count) > 0
print("OK", int(out.accept_count), int(flags.sum()), flags.size)
""",
        n_devices=4,
    )
    assert "OK" in out


def test_single_device_mesh_wave_runner_runs():
    """Degenerate 1-device mesh: WaveRunner.init hands the sharded loop a
    SCALAR fill (the shards==1 special case) — the runner must promote it to
    the rank-1 in_spec instead of crashing, and complete a run."""
    from repro.core import distributed

    ds = get_dataset("synthetic_small", num_days=DAYS)
    cfg = ABCConfig(batch_size=1024, tolerance=1.8e4, target_accepted=10,
                    chunk_size=1024, max_runs=5, num_days=DAYS,
                    wave_loop="device")
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    wr = distributed.make_wave_runner(mesh, ds, cfg, style="shard_map")
    assert wr.shards == 1
    post = run_abc(ds, cfg, key=0, wave_runner=wr)
    assert len(post) >= cfg.target_accepted
    # the carry round-trips through carry_of (scalar fill) and back
    out = wr(jax.random.PRNGKey(0), 0, wr.init(ABCState(n_params=wr.n_params)),
             2)
    carry = wr.carry_of(out)
    assert np.asarray(carry[3]).ndim == 0  # scalar fill for shards == 1
    wr(jax.random.PRNGKey(0), 2, carry, 2)
