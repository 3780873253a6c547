"""Compile the main path for a described TPU v5e chip, without the chip.

The CPU tests run the Pallas kernel in interpret mode, which accepts
layouts and memory use that the chip's compiler (Mosaic) refuses. These
tests compile the kernel and the paper-size wave loop with
`interpret=False` for one chip of a described `v5e:2x2` topology, and check
that the kernel is in the program (`tpu_custom_call`) and that the program
fits the chip's 16 GB. The sharded wave loop and SMC round are compiled for
all four chips, and their per-wave accept-count all-reduce must stay inside
the loop that tests it. The `xla_fused` day scan at the paper's size must
carry its state as rows, with no concatenate of the state in its loop.
Nothing runs, so they say nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU compiler's library at a time, and every
test worker imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import distributed
from repro.core.abc import ABCConfig, ABCState, make_simulator, make_wave_runner
from repro.core.priors import schedule_prior
from repro.core.smc import SMCConfig, make_sharded_smc_round_fn
from repro.core.summaries import N_FLAGS
from repro.epi import engine
from repro.epi.data import get_dataset
from repro.epi.models import get_model
from repro.epi.spec import EpiModelConfig, InterventionSchedule, regionalize
from repro.kernels import abc_sim, ops

HBM_BYTES = 16e9  # one v5e chip
DAYS = 49


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _check_compiled(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, total


def _metapop_r10():
    # 8 + 20 weight lanes + 100 mobility lanes: the kernel's lane budget
    return regionalize(get_model("metapop_seir"), 10, "ring:0.1")


@pytest.mark.parametrize("model,batch,tile,schedule", [
    ("siard", 100_352, 1024, None),
    ("siard", 100_352, 4096, None),
    ("siard", 100_352, 1024,
     InterventionSchedule.inferred(("alpha", "beta"), (15, 30))),
    ("metapop_seir", 8_192, 1024, None),
    ("metapop_r10", 8_192, 1024, None),
], ids=["siard-t1024", "siard-t4096", "siard-2windows", "metapop-r4",
        "metapop-r10"])
def test_kernel_compiles_for_v5e(one_chip, model, batch, tile, schedule):
    spec = _metapop_r10() if model == "metapop_r10" else get_model(model)
    assert abc_sim.kernel_lane_budget_ok(spec, 1)
    sched = schedule.shape(spec) if schedule is not None else None
    n_chan = abc_sim.n_summary_channels(spec, 1)
    n_mob = spec.n_regions if spec.is_regional else 0

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (
        arg((batch, abc_sim.theta_width(spec, sched)), jnp.float32),
        arg((), jnp.uint32),
        arg((n_chan, DAYS), jnp.float32),
        arg((sched.n_windows if sched else 0,), jnp.int32),
        arg((n_chan,), jnp.float32),
        arg((), jnp.float32),
        arg((N_FLAGS,), jnp.int32),
        arg((n_mob, n_mob), jnp.float32),
    )
    compiled = ops._abc_sim_distance_jit.lower(
        *args, population=6e7, a0=100.0, r0=0.0, d0=0.0, tile=tile,
        interpret=False, model=spec, sched=sched, pool=1,
    ).compile()
    _check_compiled(compiled)


def test_pallas_device_wave_loop_compiles_for_v5e(one_chip):
    """The jitted one-chip device wave loop at the paper's size."""
    ds = get_dataset("italy", num_days=DAYS)
    cfg = ABCConfig(batch_size=100_000, chunk_size=10_000, tolerance=1e5,
                    num_days=DAYS, backend="pallas", interpret=False,
                    wave_loop="device", max_runs=50)
    prior = schedule_prior(get_model(cfg.model), None)
    wr = make_wave_runner(prior, make_simulator(ds, cfg), cfg)
    th, d, n0, fill0 = wr.init(ABCState(n_params=prior.dim))

    def arg(x):
        x = jnp.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    compiled = wr.fn.lower(
        arg(jax.random.PRNGKey(0)), arg(jnp.int32(0)), arg(th), arg(d),
        arg(n0), arg(fill0), arg(jnp.int32(cfg.max_runs)),
        arg(jnp.float32(cfg.tolerance)), None,
    ).compile()
    _check_compiled(compiled)


def _computations(hlo: str) -> dict:
    """Optimized-HLO computation name -> its text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) ", line)
        if head and line.rstrip().endswith("{"):
            name = head.group(1)
            comps[name] = ""
        elif name is not None:
            comps[name] += line + "\n"
    return comps


def _loop_text(hlo: str) -> str:
    """The while bodies and every computation they call, transitively."""
    comps = _computations(hlo)
    todo = re.findall(r"body=%([\w.\-]+)", hlo)
    seen = set()
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            todo += re.findall(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)",
                               comps[name])
    return "".join(comps[n] for n in seen)


def test_fused_day_scan_concatenates_no_state_for_v5e(one_chip):
    """The `xla_fused` day scan at the paper's size carries rows: its loop
    holds no concatenate into the [B, 6] state or the [B, 5] hazards."""
    spec = get_model("siard")
    batch = 100_000
    cfg = EpiModelConfig(population=6e7, num_days=DAYS, a0=100.0)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda th, key, obs: engine.simulate_observed_lowmem(
            spec, th, key, cfg, obs)
    ).lower(
        arg((batch, spec.n_params), jnp.float32), arg((2,), jnp.uint32),
        arg((spec.n_observed, DAYS), jnp.float32),
    ).compile()
    loop = _loop_text(compiled.as_text())
    assert "fusion" in loop
    for width in (spec.n_state, spec.n_transitions):
        assert not re.search(
            rf"= f32\[{batch},{width}\]\S* concatenate\(", loop), width


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.asarray(topo.devices), ("data",))


def _loop_bodies_with_all_reduce(hlo: str) -> set:
    """Names of while-loop bodies that hold an all-reduce."""
    holders, name = set(), None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) ", line)
        if head and line.rstrip().endswith("{"):
            name = head.group(1)
        elif re.search(r" all-reduce(-start)?\(", line):
            holders.add(name)
    return holders & set(re.findall(r"body=%([\w.\-]+)", hlo))


def test_sharded_wave_loop_keeps_its_count_all_reduce_in_the_loop(four_chips):
    """The stop test reads the global accept count: if the compiler moved
    the count's all-reduce out of the loop, each chip would stop on its own
    count and the sharded run would leave the one-chip reference."""
    mesh = four_chips
    rep, shd = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    ds = get_dataset("italy", num_days=DAYS)
    cfg = ABCConfig(batch_size=4 * 100_000, chunk_size=10_000,
                    tolerance=1e5, num_days=DAYS, wave_loop="device",
                    max_runs=8)
    prior = schedule_prior(get_model(cfg.model), None)
    wr = distributed.make_shardmap_wave_runner(
        mesh, prior, make_simulator(ds, cfg), cfg)
    th, d, n0, fills = wr.init(ABCState(n_params=prior.dim))

    def arg(x, sharding):
        x = jnp.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    compiled = wr.fn.lower(
        arg(jax.random.PRNGKey(0), rep), arg(jnp.int32(0), rep),
        arg(th, shd), arg(d, shd), arg(n0, rep), arg(fills, shd),
        arg(jnp.int32(cfg.max_runs), rep),
        arg(jnp.float32(cfg.tolerance), rep), None,
    ).compile()
    assert _loop_bodies_with_all_reduce(compiled.as_text())


def test_sharded_smc_round_keeps_its_count_all_reduce_in_the_loop(
        four_chips):
    mesh = four_chips
    rep = NamedSharding(mesh, P())
    ds = get_dataset("italy", num_days=DAYS)
    scfg = SMCConfig(n_particles=1_000, batch_size=4 * 8_192, num_days=DAYS,
                     wave_loop="device")
    acfg = ABCConfig(batch_size=scfg.batch_size, tolerance=np.inf,
                     chunk_size=8_192, num_days=DAYS)
    prior = schedule_prior(get_model(acfg.model), None)
    round_fn = make_sharded_smc_round_fn(
        mesh, make_simulator(ds, acfg), prior, scfg)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    compiled = round_fn.lower(
        arg((2,), jnp.uint32), arg((scfg.n_particles, prior.dim), jnp.float32),
        arg((scfg.n_particles,), jnp.float32), arg((prior.dim,), jnp.float32),
        arg((), jnp.float32), arg((), jnp.int32),
    ).compile()
    assert _loop_bodies_with_all_reduce(compiled.as_text())
