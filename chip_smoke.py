#!/usr/bin/env python3
"""Smoke test of the paper's national ABC fit on a TPU.

    python chip_smoke.py              # one chip: fit, kernel check, forecast
    python chip_smoke.py --chips 4    # four chips: sharded wave loop + SMC

One chip runs, through the normal entry points and at the paper's size
(siard on `italy`, 49 days, waves of 100,000 simulations, 100 accepted):

  1. device check: the first device must be a TPU, else exit 1;
  2. the fit on the `xla_fused` backend (`run_abc`, device wave loop), with
     epsilon from `calibrate_tolerance` at quantile 1e-3 of a pilot wave;
  3. the same fit on the compiled Pallas kernel (`interpret=False`);
  4. the kernel against its pure-jnp oracle (`kernels/ref.py`) at
     8,192 x 49 on siard and metapop_seir;
  5. a 28-day `posterior_forecast` from the fit of phase 2.

`--chips 4` runs only the sharded path: the shard_map wave loop at 100,000
simulations per chip against the one-chip lockstep reference
(`scaling.make_reference_wave_runner`), required bit-identical, and one
sharded SMC fit run twice, required full and repeatable.

Every phase prints its own lines; the last line of a run that passed every
phase is one JSON object naming the device. A failed check raises, so the
process exits non-zero without that line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

SEED = 0
DAYS = 49
BATCH = 100_000  # ABCConfig defaults: the paper's wave size
CHUNK = 10_000
TARGET = 100
MAX_RUNS = 50
QUANTILE = 1e-3
KERNEL_BATCH = 8_192
HORIZON = 28
#: kernel-vs-oracle tolerance of tests/test_kernel_abc_sim.py
RTOL, ATOL = 2e-6, 1e-3
#: largest share of samples allowed outside (RTOL, ATOL) on the chip, where
#: Mosaic and XLA may round log/cos/sqrt differently and floor() can then
#: move one daily count by one. Measured on a v5e: 0 (siard) and 0.002
#: (metapop_seir, 588 floors per sample); a precision defect such as a
#: bf16 contraction in the oracle put 0.81 outside
MAX_OUTSIDE_SHARE = 0.01
#: posterior means of two backends (different random streams) agree to
#: this share of the prior width (the bar of tests/test_posterior_recovery)
MEAN_AGREEMENT = 0.25


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def timed(fn):
    """(result, seconds) of `fn()`, the clock stopped after the device is
    done with every array in the result."""
    import jax

    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


# ---------------------------------------------------------------- one chip

def fit(ds, cfg, label: str, prior):
    """run_abc twice through one compiled wave runner: the first call
    includes compilation, the second is steady state and must repeat it."""
    import numpy as np

    from repro.core.abc import make_simulator, make_wave_runner, run_abc

    wr = make_wave_runner(prior, make_simulator(ds, cfg), cfg)

    def run():
        post = run_abc(ds, cfg, key=SEED, prior=prior, wave_runner=wr)
        return post, (post.theta, post.distances)

    (post, _), t_first = timed(run)
    print(f"[fit {label}] compile+first_call_s={t_first:.3f}")
    (again, _), t_steady = timed(run)
    print(f"[fit {label}] steady_s={t_steady:.3f} waves={again.runs} "
          f"simulations={again.simulations}")
    check(np.array_equal(post.theta, again.theta)
          and np.array_equal(post.distances, again.distances),
          f"{label}: a second same-seed run accepted a different set")
    return post, wr


def check_posterior(post, cfg, prior, label: str):
    import numpy as np

    check(len(post) >= TARGET,
          f"{label}: {len(post)} accepted < target {TARGET} within "
          f"{cfg.max_runs} waves")
    # the final wave's overshoot is kept by run_abc; the posterior is the
    # TARGET nearest, as Posterior.top returns them
    top = post.top(TARGET)
    check(len(top) == TARGET, f"{label}: top({TARGET}) has {len(top)} rows")
    d = np.asarray(top.distances)
    check(bool(np.isfinite(d).all()), f"{label}: non-finite distance")
    check(float(d.max()) <= cfg.tolerance,
          f"{label}: distance {d.max()} above epsilon {cfg.tolerance}")
    mean = np.asarray(top.theta).mean(axis=0)
    lo, hi = np.asarray(prior.lows), np.asarray(prior.highs)
    check(bool(((mean >= lo) & (mean <= hi)).all()),
          f"{label}: posterior mean {mean} outside the prior box")
    print(f"[fit {label}] accepted={len(post)} kept={TARGET} "
          f"max_distance={float(d.max()):.6g} epsilon={cfg.tolerance:.6g} "
          f"mean={np.array2string(mean, precision=4)}")
    return top


def phase_fits(prior, ds):
    import dataclasses

    import jax
    import numpy as np

    from repro.core.abc import ABCConfig, ABCState, calibrate_tolerance

    base = ABCConfig(
        batch_size=BATCH, chunk_size=CHUNK, target_accepted=TARGET,
        num_days=DAYS, model="siard", backend="xla_fused",
        strategy="outfeed", wave_loop="device", max_runs=MAX_RUNS,
    )
    eps, t_cal = timed(lambda: calibrate_tolerance(
        ds, base, key=SEED, quantile=QUANTILE, n_pilot=BATCH, prior=prior))
    print(f"[calibrate] epsilon={eps:.6g} quantile={QUANTILE:g} "
          f"pilot={BATCH} compile+run_s={t_cal:.3f}")
    cfg = dataclasses.replace(base, tolerance=eps)

    post_x, _ = fit(ds, cfg, "xla_fused", prior)
    top_x = check_posterior(post_x, cfg, prior, "xla_fused")

    cfg_p = dataclasses.replace(cfg, backend="pallas", interpret=False)
    post_p, wr = fit(ds, cfg_p, "pallas", prior)
    carry = wr.init(ABCState(n_params=prior.dim))
    text = wr.fn.lower(
        jax.random.PRNGKey(SEED), np.int32(0), *carry, np.int32(MAX_RUNS),
        np.float32(eps), None,
    ).as_text()
    check("tpu_custom_call" in text,
          "pallas: no tpu_custom_call in the lowered wave loop")
    top_p = check_posterior(post_p, cfg_p, prior, "pallas")
    width = np.asarray(prior.highs) - np.asarray(prior.lows)
    gap = np.abs(np.asarray(top_p.theta).mean(0)
                 - np.asarray(top_x.theta).mean(0)) / width
    check(bool((gap <= MEAN_AGREEMENT).all()),
          f"pallas vs xla_fused posterior means differ by {gap} prior widths")
    print(f"[fit pallas] tpu_custom_call=yes mean_gap_vs_xla_fused_max="
          f"{float(gap.max()):.4f} (bar {MEAN_AGREEMENT} prior widths)")
    return cfg, top_x


def phase_kernel():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.epi.data import get_dataset
    from repro.epi.models import get_model
    from repro.kernels import ops, ref

    for name, dataset in (("siard", "italy"),
                          ("metapop_seir", "synthetic_small")):
        spec = get_model(name)
        ds = get_dataset(dataset, num_days=DAYS, model=spec)
        mcfg = ds.model_config(DAYS)
        theta = spec.prior().sample(jax.random.PRNGKey(SEED), (KERNEL_BATCH,))
        obs = jnp.asarray(ds.observed[:, :DAYS], jnp.float32)
        common = dict(population=mcfg.population, a0=mcfg.a0, r0=mcfg.r0,
                      d0=mcfg.d0, model=spec)
        seed = np.uint32(SEED + 1)
        k, t_k = timed(lambda: ops.abc_sim_distance(
            theta, seed, obs, interpret=False, **common))
        o = ref.abc_sim_distance_ref(theta, seed, obs, **common)
        k, o = np.asarray(k), np.asarray(o)
        check(bool(np.isfinite(k).all() and np.isfinite(o).all()),
              f"kernel {name}: non-finite distances")
        dev = np.abs(k - o)
        rel = dev / np.maximum(np.abs(o), np.finfo(np.float32).tiny)
        share = float(np.mean(dev > ATOL + RTOL * np.abs(o)))
        print(f"[kernel {name}] batch={KERNEL_BATCH} days={DAYS} "
              f"compile+first_call_s={t_k:.3f} max_rel_dev={rel.max():.3e} "
              f"median_rel_dev={float(np.median(rel)):.3e} "
              f"share_outside_tol={share:.4f} (limit {MAX_OUTSIDE_SHARE})")
        check(share <= MAX_OUTSIDE_SHARE,
              f"kernel {name}: {share:.4f} of samples outside rtol={RTOL} "
              f"atol={ATOL}")


def phase_forecast(cfg, top, ds):
    import numpy as np

    from repro.launch.abc_run import posterior_forecast

    bands, t = timed(lambda: posterior_forecast(
        top.theta, ds, cfg, HORIZON, key=SEED + 1))
    total = DAYS + HORIZON
    qs = [f"q{int(round(q * 100)):02d}" for q in bands["quantiles"]]
    for name, ch in bands["channels"].items():
        b = np.asarray([ch[q] for q in qs], np.float64)
        check(b.shape == (len(qs), total),
              f"forecast {name}: bands shape {b.shape}")
        check(bool(np.isfinite(b).all()) and bool(
            np.isfinite(ch["mean"]).all()),
              f"forecast {name}: non-finite band")
        check(bool((np.diff(b, axis=0) >= 0).all()),
              f"forecast {name}: quantile bands out of order")
    print(f"[forecast] horizon={HORIZON} days={total} particles="
          f"{bands['n_particles']} channels={list(bands['channels'])} "
          f"compile+first_call_s={t:.3f} bands finite and ordered")


# ------------------------------------------------------------- four chips

def phase_sharded(prior, ds, n_chips: int):
    import dataclasses

    import jax
    import numpy as np

    from repro.core import distributed
    from repro.core.abc import (
        ABCConfig,
        ABCState,
        calibrate_tolerance,
        make_simulator,
    )
    from repro.core.scaling import device_mesh, make_reference_wave_runner
    from repro.core.smc import SMCConfig, run_smc_abc

    # a tighter quantile than the one-chip fit, so the loop runs a few
    # waves before the 4 x 100,000 batch meets the target
    cfg = ABCConfig(
        batch_size=BATCH * n_chips, chunk_size=CHUNK, target_accepted=TARGET,
        num_days=DAYS, model="siard", backend="xla_fused",
        strategy="outfeed", wave_loop="device", max_runs=8,
    )
    eps = calibrate_tolerance(ds, dataclasses.replace(cfg, batch_size=BATCH),
                              key=SEED, quantile=QUANTILE / 10,
                              n_pilot=BATCH, prior=prior)
    cfg = dataclasses.replace(cfg, tolerance=eps)
    key = jax.random.PRNGKey(SEED)
    mesh = device_mesh(n_chips)

    wr = distributed.make_wave_runner(mesh, ds, cfg, style="shard_map")
    init = ABCState(n_params=prior.dim)
    out, t_first = timed(lambda: wr(key, 0, wr.init(init), cfg.max_runs))
    out, t_steady = timed(lambda: wr(key, 0, wr.init(init), cfg.max_runs))
    shard_devs = sorted(
        (s.index[0].start or 0, str(s.device))
        for s in out.theta_buf.addressable_shards
    )
    print(f"[sharded] mesh={n_chips} batch_per_chip={BATCH} epsilon={eps:.6g} "
          f"compile+first_call_s={t_first:.3f} steady_s={t_steady:.3f}")
    print(f"[sharded] shard devices: {[d for _, d in shard_devs]}")
    check(len({d for _, d in shard_devs}) == n_chips,
          f"sharded: buffers sit on {len(shard_devs)} shards but "
          f"{len({d for _, d in shard_devs})} distinct devices")

    ref = make_reference_wave_runner(prior, make_simulator(ds, cfg), cfg,
                                     n_shards=n_chips)
    ref_out, t_ref = timed(lambda: ref(key, 0, ref.init(init), cfg.max_runs))
    fills = np.asarray(out.fill_counts)
    same = {
        "fills": np.array_equal(fills, np.asarray(ref_out.fill_counts)),
        "theta": np.array_equal(np.asarray(out.theta_buf),
                                np.asarray(ref_out.theta_buf)),
        "dist": np.array_equal(np.asarray(out.dist_buf),
                               np.asarray(ref_out.dist_buf)),
        "n_accepted": int(out.n_accepted) == int(ref_out.n_accepted),
        "waves": int(out.waves_done) == int(ref_out.waves_done),
    }
    print(f"[sharded] waves={int(out.waves_done)} "
          f"n_accepted={int(out.n_accepted)} fills={fills.tolist()}")
    print(f"[reference] waves={int(ref_out.waves_done)} "
          f"n_accepted={int(ref_out.n_accepted)} "
          f"fills={np.asarray(ref_out.fill_counts).tolist()} "
          f"one_chip_compile+run_s={t_ref:.3f} bit_identical={same}")
    check(int(out.n_accepted) > 0, "sharded: nothing accepted")
    check(all(same.values()),
          f"sharded vs one-chip reference differ: {same}")

    scfg = SMCConfig(n_particles=1_000, batch_size=BATCH * n_chips,
                     n_rounds=2, num_days=DAYS, wave_loop="device")
    a, t_a = timed(lambda: run_smc_abc(ds, scfg, key=SEED, mesh=mesh))
    b, t_b = timed(lambda: run_smc_abc(ds, scfg, key=SEED, mesh=mesh))
    check(len(a) == scfg.n_particles and bool(np.isfinite(a.distances).all()),
          f"sharded SMC: {len(a)} particles, finite="
          f"{bool(np.isfinite(a.distances).all())}")
    check(np.array_equal(a.theta, b.theta)
          and np.array_equal(a.distances, b.distances),
          "sharded SMC: same key, different population")
    print(f"[sharded smc] particles={len(a)} rounds={scfg.n_rounds} "
          f"final_epsilon={a.tolerance:.6g} "
          f"simulations={a.simulations} compile+first_call_s={t_a:.3f} "
          f"steady_s={t_b:.3f} repeatable=yes")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip fit; 4: only the sharded path")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro beside {Path(__file__).name}; run "
              "it from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    info = device_info()
    print(f"[device] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']} compile_cache={cache}")
    if info["platform"] != "tpu":
        print("chip_smoke: no TPU found; this smoke test runs on the chip "
              "only", file=sys.stderr)
        return 1
    if info["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {info['count']} "
              "device(s) visible", file=sys.stderr)
        return 1

    from repro.core.priors import schedule_prior
    from repro.epi.data import get_dataset
    from repro.epi.models import get_model

    prior = schedule_prior(get_model("siard"), None)
    ds = get_dataset("italy", num_days=DAYS, model="siard")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_sharded(prior, ds, args.chips)
            count = args.chips
        else:
            cfg, top = phase_fits(prior, ds)
            phase_kernel()
            phase_forecast(cfg, top, ds)
            count = 1
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"[done] wall_s={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": info["platform"], "kind": info["kind"], "count": count,
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
