"""Host wave loop vs device-resident wave loop: before/after throughput.

    PYTHONPATH=src python benchmarks/bench_wave_loop.py [--batch 8192] [--waves 16]
    # nightly (backend, summary, distance) sweep:
    PYTHONPATH=src python benchmarks/bench_wave_loop.py \
        --backends xla_fused pallas --summaries identity weekly log_weekly \
        --distances euclidean mae normalized_euclidean

Runs the SAME wave budget (target_accepted unreachable, max_runs fixed)
through both drivers of `run_abc`:

  host   — one jitted wave per call, host harvest after every wave
           (the per-wave host sync the paper's outfeed host code pays)
  device — one jitted lax.while_loop over all waves with donated accept
           buffers; a single host sync at the end

Both see identical sample streams (pinned by tests/test_wave_loop.py), so the
delta is pure loop/dispatch overhead. The grid additionally sweeps the
summary-statistic and distance axes (core.summaries): every cell records
`cost_vs_identity_euclidean`, the device-loop throughput of that
(summary, distance) pair relative to the identity+euclidean cell of the same
(model, backend) — the number that tracks what non-euclidean statistics cost
the fused paths over time (the nightly JSON artifact carries it). The JSON
artifact also embeds the raw simulator throughput from
experiments/bench/model_sweep.json (when present) so regressions against the
`bench_model_sweep` baseline are visible in one place — wave-loop sims/s can
approach but never exceed the raw simulator.
"""

import argparse
import json
import sys
from pathlib import Path

import jax

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _harness import (  # noqa: E402
    RESULTS_DIR,
    best_of,
    emit_artifact,
    render_table,
    roofline_fields,
)

from repro.core.abc import ABCConfig, make_simulator, run_abc  # noqa: E402
from repro.epi.data import get_dataset  # noqa: E402
from repro.epi.models import get_model  # noqa: E402

DAYS = 20


def calibrate(ds, model, backend, summary, distance, quantile=0.01):
    """Per-cell epsilon at ~1% acceptance so the accept path carries
    realistic traffic for every (model, summary, distance) scale — the
    production pilot-wave calibration, not a benchmark-local copy."""
    from repro.core.abc import calibrate_tolerance

    cfg = ABCConfig(batch_size=4096, num_days=DAYS, chunk_size=4096,
                    backend=backend, model=model, summary=summary,
                    distance=distance)
    return calibrate_tolerance(ds, cfg, key=42, quantile=quantile,
                               n_pilot=4096)


def make_driver(ds, cfg):
    """Pre-build the compiled runner so timing excludes trace/compile."""
    import jax as _jax

    from repro.core.abc import abc_run_batch, make_wave_runner

    prior = get_model(cfg.model).prior()
    sim = make_simulator(ds, cfg)
    if cfg.wave_loop == "device":
        runner = make_wave_runner(prior, sim, cfg)
        return lambda key: run_abc(ds, cfg, key=key, wave_runner=runner)
    run_fn = _jax.jit(abc_run_batch(prior, sim, cfg))
    return lambda key: run_abc(ds, cfg, key=key, run_fn=run_fn)


def tile_study(args, cells, parity, rows):
    """Hardwired tile=1024 vs the measured tile sweep, end to end.

    Runs the device wave loop of the FIRST requested model on the pallas
    backend at every compatible kernel tile, records one gated cell per
    tile (`tile_study/{model}/pallas/tile{t}`), persists the winner to the
    tuning cache under experiments/tuning/, and reports whether the
    autotuned tile beat the old hardwired 1024 default. The acceptance
    counts are parity-gated EQUAL across tiles: the kernel's global sample
    index makes the RNG tile-invariant, so tiling is pure scheduling.
    """
    from repro.core import tuning

    model = args.models[0]
    waves = min(args.waves, 4)  # the sweep needs relative, not long, runs
    ds = get_dataset("synthetic_small", num_days=DAYS, model=model)
    tol = calibrate(ds, model, "pallas", "identity", "euclidean")
    target = waves * args.batch + 1
    cands = tuning.tile_candidates(args.batch)
    if 1024 not in cands and args.batch % 1024 == 0:
        cands = tuple(sorted(set(cands) | {1024}))
    study = {"model": model, "batch": args.batch, "waves": waves,
             "tiles": {}, "default_tile": 1024}
    for t in cands:
        cfg = ABCConfig(
            batch_size=args.batch, tolerance=tol, target_accepted=target,
            max_runs=waves, chunk_size=args.batch, num_days=DAYS,
            backend="pallas", model=model, wave_loop="device", tile=int(t),
        )
        driver = make_driver(ds, cfg)
        post, dt = best_of(driver, 1, reps=args.reps, warmup=1)
        key = f"tile_study/{model}/pallas/tile{t}"
        cells[key] = {
            "wall_s": dt, "simulations": post.simulations,
            "sims_per_s": post.simulations / dt, "tile": int(t),
            **roofline_fields(model, DAYS, post.simulations, dt),
        }
        # tile invariance is the contract: same accepted count AND same
        # simulation budget at every tile, exact-gated
        parity[key] = {"simulations": post.simulations,
                       "n_accepted": len(post)}
        study["tiles"][str(t)] = {"wall_s": dt,
                                  "sims_per_s": post.simulations / dt}
        rows.append([model, "pallas", f"tile={t}", "euclidean", "device",
                     f"{dt*1e3:.1f}", f"{post.simulations / dt:,.0f}"])
    best = min(study["tiles"], key=lambda k: study["tiles"][k]["wall_s"])
    study["autotuned_tile"] = int(best)
    d1024 = study["tiles"].get("1024")
    study["autotuned_beats_default"] = bool(
        d1024 is not None and study["tiles"][best]["wall_s"] < d1024["wall_s"]
    )
    # persist the end-to-end winner so --autotune runs pick it up
    cfg0 = ABCConfig(batch_size=args.batch, chunk_size=args.batch,
                     num_days=DAYS, backend="pallas", model=model)
    cache = tuning.TuningCache()
    cache.put(tuning.cfg_cache_key(cfg0), {
        "schema": tuning.CACHE_SCHEMA,
        "device_kind": jax.devices()[0].device_kind,
        "backend": "pallas", "model": model,
        "days": DAYS, "batch": args.batch, "summary": "identity",
        "distance": "euclidean", "schedule": "nosched",
        "tile": int(best), "best_batch": None,
        "measurements": {f"tile{t}": v["wall_s"]
                         for t, v in study["tiles"].items()},
    })
    study["cache_path"] = str(cache.path)
    print(f"[tile-study] winner tile={best} "
          f"(beats 1024: {study['autotuned_beats_default']}); "
          f"cached -> {cache.path}")
    return study


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8192)
    ap.add_argument("--waves", type=int, default=16)
    ap.add_argument("--models", nargs="+", default=["siard", "sir"])
    ap.add_argument("--backends", nargs="+", default=["xla_fused"])
    ap.add_argument("--summaries", nargs="+", default=["identity"],
                    help="summary-statistic sweep axis (core.summaries names)")
    ap.add_argument("--distances", nargs="+", default=["euclidean"],
                    help="distance-kind sweep axis (core.summaries names)")
    ap.add_argument("--reps", type=int, default=3,
                    help="timed repetitions per cell (best-of; warmup "
                         "excluded) — single runs are too noisy to track "
                         "the summary-statistic cost")
    ap.add_argument("--out-name", default="wave_loop",
                    help="artifact basename under experiments/bench/ (the "
                         "nightly job writes the default run and the summary "
                         "sweep to separate JSON files)")
    ap.add_argument("--no-tile-study", action="store_true",
                    help="skip the pallas tile sweep (hardwired 1024 vs "
                         "measured winner) that rides along by default")
    args = ap.parse_args(argv)

    # unreachable target so both drivers burn the full wave budget, but small
    # enough that the accept buffer (target + batch rows) stays device-sized
    target = args.waves * args.batch + 1

    rows, runs = [], []
    cells, parity = {}, {}
    # identity+euclidean device-loop sims/s per (model, backend): the
    # baseline the sweep cells are costed against
    baseline: dict = {}
    grid = [(s, d) for s in args.summaries for d in args.distances]
    # the baseline cell must run FIRST (every other cell is costed against
    # it), wherever — or whether — it appeared in the requested grid
    base_pair = ("identity", "euclidean")
    if base_pair in grid:
        grid.remove(base_pair)
    grid.insert(0, base_pair)
    for model in args.models:
        ds = get_dataset("synthetic_small", num_days=DAYS, model=model)
        for backend in args.backends:
            for summary, distance in grid:
                tol = calibrate(ds, model, backend, summary, distance)
                per_loop = {}
                for loop in ("host", "device"):
                    cfg = ABCConfig(
                        batch_size=args.batch, tolerance=tol,
                        target_accepted=target, max_runs=args.waves,
                        chunk_size=args.batch, num_days=DAYS, backend=backend,
                        model=model, wave_loop=loop,
                        summary=summary, distance=distance,
                    )
                    driver = make_driver(ds, cfg)
                    post, dt = best_of(driver, 1, reps=args.reps, warmup=1)
                    sims_per_s = post.simulations / dt
                    per_loop[loop] = {
                        "wall_s": dt, "simulations": post.simulations,
                        "sims_per_s": sims_per_s,
                        # roofline instrumentation: measured throughput vs the
                        # analytic ceiling of THIS (model, summary, distance)
                        **roofline_fields(model, DAYS, post.simulations, dt,
                                          summary=summary, distance=distance),
                    }
                    if backend == "pallas":
                        from repro.kernels.ops import resolve_tile

                        # surface the kernel tile actually used in the cell
                        per_loop[loop]["tile"] = resolve_tile(
                            args.batch, cfg.tile
                        )
                    key = f"{model}/{backend}/{summary}/{distance}/{loop}"
                    cells[key] = dict(per_loop[loop])
                    # the wave budget is fixed (unreachable target), so the
                    # simulation count is deterministic — a parity metric
                    parity[key] = post.simulations
                    rows.append([model, backend, summary, distance, loop,
                                 f"{dt*1e3:.1f}", f"{sims_per_s:,.0f}"])
                speedup = (per_loop["device"]["sims_per_s"]
                           / per_loop["host"]["sims_per_s"])
                if (summary, distance) == ("identity", "euclidean"):
                    baseline[(model, backend)] = per_loop["device"]["sims_per_s"]
                base = baseline.get((model, backend))
                cost = (per_loop["device"]["sims_per_s"] / base) if base else None
                runs.append({
                    "model": model, "backend": backend, "summary": summary,
                    "distance": distance, **per_loop,
                    "device_over_host_speedup": speedup,
                    # < 1.0 = this statistic costs fused throughput vs the
                    # paper's raw euclidean; the nightly artifact tracks it
                    "cost_vs_identity_euclidean": cost,
                })
                rows.append([model, backend, summary, distance, "speedup", "",
                             f"{speedup:.2f}x"])

    # pallas tile sweep: hardwired 1024 vs measured winner, gated per tile
    study = None
    if not args.no_tile_study:
        study = tile_study(args, cells, parity, rows)

    # legacy payload fields (and the raw-simulator baseline, so one artifact
    # shows the trajectory) ride along outside the gated envelope
    extra = {"batch": args.batch, "waves": args.waves, "reps": args.reps,
             "runs": runs}
    if study is not None:
        extra["tile_study"] = study
    sweep_path = RESULTS_DIR / "model_sweep.json"
    if sweep_path.exists():
        extra["model_sweep_baseline"] = json.loads(sweep_path.read_text())

    print(render_table(
        ["model", "backend", "summary", "distance", "loop", "wall_ms",
         "sims/s"], rows))
    # basename only: the artifact always lands under experiments/bench/
    path = emit_artifact(
        Path(args.out_name).name,
        cells=cells,
        parity=parity,
        meta={"batch": args.batch, "waves": args.waves, "reps": args.reps,
              "models": args.models, "backends": args.backends,
              "summaries": args.summaries, "distances": args.distances},
        extra=extra,
    )
    print(f"\nsaved {path}")
    return extra


if __name__ == "__main__":
    main()
