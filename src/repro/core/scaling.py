"""Multi-device scaling-study executor (the paper's 16-IPU experiment, §4.5).

The paper's headline systems claim is that the ABC framework "scales across
16 IPUs, with scaling overhead not exceeding 8%". This module reproduces
that experiment on ANY JAX device set — real accelerators or simulated host
devices (`XLA_FLAGS=--xla_force_host_platform_device_count=8`):

  * `device_mesh(n)` carves a 1-axis data mesh out of the first `n` devices,
    so one process measures every device count of the curve (disjoint
    subsets of the same device pool, exactly how the paper sweeps 1..16
    IPUs on one machine);
  * `run_scaling_cell` times the device-resident shard_map wave loop
    (`distributed.make_wave_runner`) over a fixed wave budget with an
    unreachable acceptance target, so every device count burns the same
    per-device work and the measured delta is pure scaling overhead
    (collective stop psum + host gather of the per-shard accept buffers);
  * `run_scaling_study` sweeps (model, backend) x device-count under WEAK
    scaling (global batch = n * batch_per_device, the paper's "2x100k means
    100k per IPU" convention) and derives the two headline metrics per cell:

        parallel_efficiency  = sims_per_s(n) / (n * sims_per_s(n_ref))
        scaling_overhead_pct = (1 - parallel_efficiency) * 100

    — the reproduction's analogue of the paper's Figure on 16-IPU scaling
    (the paper reports <= 8% overhead at n=16).

Correctness contract: `make_reference_wave_runner` executes the N-shard
wave-loop program LOCKSTEP ON ONE DEVICE — same per-(wave, shard) fold_in
keys, same per-shard accept buffers, same global stop condition — so the
sharded runner's accepted sets can be pinned bit-identical per shard against
a single-device run (tests/test_scaling.py). Scaling never changes the
statistics, only the wall clock.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.abc import (
    ABCConfig,
    WaveLoopOutput,
    WaveRunner,
    calibrate_tolerance,
    run_abc,
    wave_capacity,
    wave_loop_body,
)
from repro.core.priors import UniformBoxPrior
from repro.epi.data import get_dataset


def device_mesh(n: int, devices: Optional[Sequence] = None) -> Mesh:
    """A 1-axis data mesh over the FIRST `n` devices of the pool.

    Prefix subsets keep every device count of a study inside one process:
    the n=1 cell and the n=8 cell share device 0, exactly like the paper's
    1..16-IPU sweep on one machine.
    """
    devices = list(devices if devices is not None else jax.devices())
    if n > len(devices):
        raise ValueError(
            f"requested {n} devices but only {len(devices)} are visible; on "
            "CPU, simulate more with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N"
        )
    return Mesh(np.asarray(devices[:n]), ("data",))


def make_reference_wave_runner(
    prior: UniformBoxPrior,
    simulator,
    cfg: ABCConfig,
    n_shards: int,
) -> WaveRunner:
    """The N-shard wave-loop program executed lockstep on ONE device.

    Each wave advances every shard's segment with that shard's own stream —
    `fold_in(fold_in(key, run_idx0 + w), shard)`, the exact keying of
    `distributed.make_shardmap_wave_runner` — and the global stop condition
    sums the per-shard accepts exactly like the sharded runner's psum. The
    per-shard accept buffers are therefore BIT-IDENTICAL to an N-device run
    with the same seed (pinned in tests/test_scaling.py): the reference that
    makes multi-device speedups trustworthy.
    """
    if cfg.batch_size % n_shards:
        raise ValueError(
            f"batch_size {cfg.batch_size} not divisible by {n_shards} shards"
        )
    local_b = cfg.batch_size // n_shards
    cap = wave_capacity(cfg, local_b)
    target = cfg.target_accepted
    sim_call = lambda th, k, _data: simulator(th, k)  # noqa: E731
    bodies = [
        wave_loop_body(
            prior, sim_call, local_b, cap,
            fold_axis=(lambda d=d: jnp.int32(d)),
        )
        for d in range(n_shards)
    ]

    def loop(key, run_idx0, theta_buf, dist_buf, n0, fills, max_waves,
             tolerance, data):
        run_idx0 = jnp.asarray(run_idx0, jnp.int32)
        max_waves = jnp.asarray(max_waves, jnp.int32)
        n0 = jnp.asarray(n0, jnp.int32)
        # rank-1 even for one shard, where WaveRunner.init hands back a scalar
        fills = jnp.atleast_1d(jnp.asarray(fills, jnp.int32))

        def cond(carry):
            w, n_global, *_ = carry
            return jnp.logical_and(n_global < target, w < max_waves)

        def body(carry):
            w, n_global, fills, th, d = carry
            n_run = n_global
            for s in range(n_shards):  # unrolled: one segment per shard
                lo = s * cap
                carry_s = (w, n_run, fills[s],
                           jax.lax.dynamic_slice_in_dim(th, lo, cap),
                           jax.lax.dynamic_slice_in_dim(d, lo, cap))
                _, n_run, fill_s, th_s, d_s = bodies[s](
                    carry_s, key, run_idx0, tolerance, data
                )
                th = jax.lax.dynamic_update_slice_in_dim(th, th_s, lo, 0)
                d = jax.lax.dynamic_update_slice_in_dim(d, d_s, lo, 0)
                fills = fills.at[s].set(fill_s)
            return (w + 1, n_run, fills, th, d)

        w, n, fills, th_buf, d_buf = jax.lax.while_loop(
            cond, body, (jnp.int32(0), n0, fills, theta_buf, dist_buf)
        )
        return WaveLoopOutput(th_buf, d_buf, n, w, jnp.minimum(fills, cap))

    return WaveRunner(
        fn=jax.jit(loop, donate_argnums=(2, 3)),
        capacity=cap,
        shards=n_shards,
        n_params=prior.dim,
        cfg=cfg,
    )


# --------------------------------------------------------------------------
# The study
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ScalingConfig:
    """One scaling study: (model, backend) x device-count grid, weak scaling."""

    device_counts: Tuple[int, ...] = (1, 2, 4, 8)
    models: Tuple[str, ...] = ("siard",)
    backends: Tuple[str, ...] = ("xla_fused",)
    #: per-DEVICE batch; the global batch of the n-device cell is n * this
    #: (the paper's "2x100k" = 100k per IPU convention)
    batch_per_device: int = 4096
    #: fixed wave budget per measurement (the acceptance target is set
    #: unreachable so every cell burns exactly this many waves)
    waves: int = 8
    num_days: int = 20
    dataset: str = "synthetic_small"
    #: timed repetitions per cell, best-of (excludes the compile/warmup run)
    reps: int = 3
    #: pilot-quantile for the epsilon so the accept/compact path carries
    #: realistic traffic in every cell (an accept-nothing epsilon would hide
    #: the gather cost the paper's outfeed pays)
    tolerance_quantile: float = 0.01
    style: str = "shard_map"
    #: hot-path tuning knobs (repro.core.tuning), threaded into each cell's
    #: ABCConfig: explicit Pallas tile / xla_fused scan unroll, or
    #: autotune=True to resolve cached measured winners per cell shape
    tile: Optional[int] = None
    scan_unroll: Optional[int] = None
    autotune: bool = False

    def __post_init__(self):
        if not self.device_counts:
            raise ValueError("device_counts must be non-empty")
        if self.style not in ("shard_map", "pjit"):
            raise ValueError(f"unknown runner style {self.style!r}")


def cell_key(model: str, backend: str, batch_per_device: int, n: int) -> str:
    return f"{model}/{backend}/b{batch_per_device}/n{n}"


def _cell_abc_config(scfg: ScalingConfig, model: str, backend: str,
                     n: int, tolerance: float) -> ABCConfig:
    global_batch = n * scfg.batch_per_device
    return ABCConfig(
        batch_size=global_batch,
        tolerance=tolerance,
        # unreachable: every cell runs the full wave budget
        target_accepted=scfg.waves * global_batch + 1,
        strategy="outfeed",
        chunk_size=global_batch,
        max_runs=scfg.waves,
        num_days=scfg.num_days,
        backend=backend,
        model=model,
        wave_loop="device",
        tile=scfg.tile if backend == "pallas" else None,
        scan_unroll=scfg.scan_unroll if backend == "xla_fused" else None,
        autotune=scfg.autotune,
    )


def run_scaling_cell(
    dataset,
    cfg: ABCConfig,
    mesh: Mesh,
    reps: int = 3,
    style: str = "shard_map",
    key: int = 1,
) -> Dict[str, float]:
    """Time the sharded device-resident wave loop for one cell.

    Returns best-of-`reps` wall clock plus throughput; the warmup run (which
    pays trace + compile) is excluded. Accept statistics ride along so the
    caller can assert device-count invariance.
    """
    from repro.core import distributed

    runner = distributed.make_wave_runner(mesh, dataset, cfg, style=style)
    run_abc(dataset, cfg, key=0, wave_runner=runner)  # warmup: compile
    best, post = None, None
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        post = run_abc(dataset, cfg, key=key, wave_runner=runner)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return {
        "wall_s": best,
        "simulations": int(post.simulations),
        "sims_per_s": post.simulations / best,
        "waves": int(post.runs),
        "n_accepted": int(len(post)),
        "accept_rate": len(post) / max(post.simulations, 1),
    }


def run_scaling_study(
    scfg: ScalingConfig,
    devices: Optional[Sequence] = None,
    verbose: bool = False,
) -> Dict:
    """Sweep the (model, backend) x device-count grid on this process's
    devices; returns the report dict (see benchmarks/bench_scaling.py for
    the artifact + regression-gate wrapping).

    Efficiency is relative to the SMALLEST device count in the sweep
    (normally 1): `parallel_efficiency = tp_n * n_ref / (tp_ref * n)` under
    weak scaling, and `scaling_overhead_pct = (1 - efficiency) * 100` — the
    number the paper bounds by 8% at 16 IPUs. On a single physical core the
    simulated-device curve measures dispatch/collective overhead only; on
    real accelerators it measures the paper's claim.
    """
    devices = list(devices if devices is not None else jax.devices())
    counts = sorted(set(scfg.device_counts))
    n_ref = counts[0]
    report: Dict = {
        "config": dataclasses.asdict(scfg),
        "n_visible_devices": len(devices),
        "device_kind": devices[0].device_kind if devices else "none",
        "reference_device_count": n_ref,
        "cells": {},
    }
    for model in scfg.models:
        ds = get_dataset(scfg.dataset, num_days=scfg.num_days, model=model)
        for backend in scfg.backends:
            # one epsilon per (model, backend), calibrated at the per-device
            # batch so every device count accepts at the same expected rate
            cal_cfg = ABCConfig(
                batch_size=scfg.batch_per_device, tolerance=1.0,
                chunk_size=scfg.batch_per_device, num_days=scfg.num_days,
                backend=backend, model=model,
            )
            tol = calibrate_tolerance(
                ds, cal_cfg, key=42, quantile=scfg.tolerance_quantile,
                n_pilot=scfg.batch_per_device,
            )
            ref_tp = None
            for n in counts:
                mesh = device_mesh(n, devices)
                cfg = _cell_abc_config(scfg, model, backend, n, tol)
                cell = run_scaling_cell(
                    ds, cfg, mesh, reps=scfg.reps, style=scfg.style
                )
                if n == n_ref:
                    ref_tp = cell["sims_per_s"]
                eff = cell["sims_per_s"] * n_ref / (ref_tp * n)
                cell.update({
                    "model": model, "backend": backend, "devices": n,
                    "batch_per_device": scfg.batch_per_device,
                    "global_batch": n * scfg.batch_per_device,
                    "tolerance": tol,
                    "parallel_efficiency": eff,
                    "scaling_overhead_pct": (1.0 - eff) * 100.0,
                })
                report["cells"][cell_key(
                    model, backend, scfg.batch_per_device, n)] = cell
                if verbose:
                    print(f"[scaling] {model}/{backend} n={n}: "
                          f"{cell['sims_per_s']:,.0f} sims/s, "
                          f"eff={eff:.3f}, "
                          f"overhead={cell['scaling_overhead_pct']:.1f}%")
    return report


def format_report(report: Dict) -> str:
    """Render the throughput-vs-device-count curves as a table."""
    headers = ["model", "backend", "devices", "global_batch", "wall_ms",
               "sims/s", "efficiency", "overhead_%"]
    rows: List[List[str]] = []
    for cell in report["cells"].values():
        rows.append([
            cell["model"], cell["backend"], str(cell["devices"]),
            str(cell["global_batch"]), f"{cell['wall_s'] * 1e3:.1f}",
            f"{cell['sims_per_s']:,.0f}",
            f"{cell['parallel_efficiency']:.3f}",
            f"{cell['scaling_overhead_pct']:.1f}",
        ])
    widths = [max(len(h), max((len(r[i]) for r in rows), default=0))
              for i, h in enumerate(headers)]

    def fmt(row):
        return " | ".join(c.rjust(w) for c, w in zip(row, widths))

    sep = "-+-".join("-" * w for w in widths)
    return "\n".join([fmt(headers), sep] + [fmt(r) for r in rows])
