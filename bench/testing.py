"""A run of a cell on the CPU at a size a test can hold, for the tests.

    python -m bench.testing '{"workload": "siard3.deep", "seed": 3,
                              "seconds": 1, "fault": "altered"}'

`tiny_run` starts such a run in a process of its own and returns its
result line.

The cell's configuration keeps its model, datasets and traffic, with
`TINY` in place of its wave size and horizon; the harness's look for a chip
is skipped and everything else runs as `run.py` runs it. `control` names a
dtype to put the reference in that precision in the program's place;
`fault` plants one of `FAULTS` in the program's timed path. The result
line is the last line of standard output, as with `run.py`.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

#: wave size and horizon of a test run: small enough for a CPU, and
#: a multiple of the program's default outfeed chunk (10,000)
TINY = {"batch_per_chip": 10_000, "num_days": 10}


def _unchanged():
    """The wave loop returns the state it was given."""
    import jax.numpy as jnp

    from repro.core import abc

    def call(self, key, run_idx0, carry, max_waves):
        th, d, n0, fill0 = carry
        fills = jnp.atleast_1d(jnp.asarray(fill0, jnp.int32))
        return abc.WaveLoopOutput(th, d, jnp.int32(n0), jnp.int32(0), fills)

    abc.WaveRunner.__call__ = call


def _wrap_simulator(edit):
    """Every simulator the program builds passes its distances through
    `edit(distances)`."""
    from repro.core import abc, distributed

    make = abc.make_simulator

    def make_edited(dataset, cfg):
        sim = make(dataset, cfg)
        return lambda theta, key: edit(sim(theta, key))

    abc.make_simulator = make_edited
    distributed.make_simulator = make_edited


def _half_batch():
    """Half of every batch is left out: its rows never come within eps."""
    import jax.numpy as jnp

    _wrap_simulator(lambda d: jnp.where(
        jnp.arange(d.shape[0]) < d.shape[0] // 2, d, jnp.inf))


def _altered():
    """An answer is altered where it is produced: the first row of every
    batch reports distance 0."""
    _wrap_simulator(lambda d: d.at[0].set(0.0))


def _no_exchange():
    """The exchange between chips is left out: each chip stops on its own
    count of accepted rows, not on the sum over chips. Without the sum a
    chip's count is its own, so shard_map's check that the stop count is
    the same on every chip is switched off with it."""
    import functools

    import jax

    from repro.core import distributed

    jax.shard_map = functools.partial(jax.shard_map, check_vma=False)
    build = distributed.build_wave_loop

    def build_local(*args, **kwargs):
        if kwargs.get("count_all") is not None:
            kwargs["count_all"] = lambda count: count
        return build(*args, **kwargs)

    distributed.build_wave_loop = build_local


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "altered": _altered, "no_exchange": _no_exchange}


def tiny_run(cache_dir, devices: int = 1, **spec) -> tuple[dict, str]:
    """(result line, standard error) of `bench.testing` with `spec`, on
    `devices` CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]),
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    env.pop("XLA_FLAGS", None)
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    p = subprocess.run(
        [sys.executable, "-m", "bench.testing", json.dumps(spec)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    if p.returncode != 0:
        raise AssertionError(f"run failed ({p.returncode}):\n{p.stderr[-4000:]}")
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def main(spec: dict) -> None:
    import jax.numpy as jnp

    from bench import harness, run

    load = harness.load_cell

    def tiny(bench, name):
        cell = load(bench, name)
        cell.config = dict(cell.config, **TINY)
        return cell

    harness.load_cell = tiny
    if spec.get("fault"):
        FAULTS[spec["fault"]]()
    control = getattr(jnp, spec["control"]) if spec.get("control") else None
    run.run(spec["workload"], int(spec["seed"]), float(spec["seconds"]),
            bool(spec.get("trace", False)), platform="cpu", control=control)


if __name__ == "__main__":
    main(json.loads(sys.argv[1]))
