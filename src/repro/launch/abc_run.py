"""The paper's workload driver: parallel ABC inference of the epidemiology
model, with multi-device sharding, checkpoint/resume and backend selection.

    PYTHONPATH=src python -m repro.launch.abc_run --dataset synthetic_small \
        --tolerance 1.6e4 --accept 100 --batch 8192 --days 20

    # paper §5 workflow (scaled): all three countries
    PYTHONPATH=src python -m repro.launch.abc_run --dataset italy --days 49 ...

    # any registered compartmental model (see repro.epi.models); synthetic
    # ground truth is generated from the chosen model's spec
    PYTHONPATH=src python -m repro.launch.abc_run --model seir \
        --dataset synthetic_small --auto-tolerance 1e-3 --batch 8192

    # campaign mode: fan a dataset x model x backend x seed grid across the
    # host's devices, one compiled wave loop per unique shape, per-scenario
    # checkpoint/resume and one aggregated report (see README)
    PYTHONPATH=src python -m repro.launch.abc_run --campaign \
        --datasets italy new_zealand usa --models siard seiard \
        --auto-tolerance 1e-3 --accept 100 --out experiments/campaigns/demo

    # amortized inference: train an NPE estimator instead of running waves
    # (backend=npe; --npe-* flags size the estimator, docs/ARCHITECTURE.md)
    PYTHONPATH=src python -m repro.launch.abc_run --backend npe \
        --model sir --dataset synthetic_small --days 20 --accept 256

    # strong/weak scaling study of the wave loop (bench-artifact/v1 JSON)
    PYTHONPATH=src python -m repro.launch.abc_run --scaling \
        --models siard --backends xla_fused --scaling-devices 1 2 4

    # posterior-predictive forecast bands (optionally counterfactual)
    PYTHONPATH=src python -m repro.launch.abc_run --dataset italy \
        --intervention "alpha@25=0.1:1" --forecast 14 \
        --forecast-schedule none --forecast-out bands.json

Flag families (full list: --help): single-run fitting (--dataset --model
--days --batch --accept --tolerance/--auto-tolerance --strategy --summary
--distance --intervention --seed), backend selection (--backend xla |
xla_fused | pallas | npe, --tile --scan-unroll --autotune --interpret,
--npe-steps --npe-batch --npe-hidden --npe-components), spatial
metapopulation (--regions --mobility), checkpoint/resume (--state),
multi-device (--multi-device --wave-loop), campaign grids (--campaign
--datasets --models --backends --seeds --interventions --summaries --out),
scaling studies (--scaling --scaling-devices --scaling-waves --scaling-reps
--scaling-out), and forecasting (--forecast --forecast-schedule
--forecast-out).
"""

from __future__ import annotations

import argparse
import json

from repro.compile_cache import enable_compile_cache
from repro.core.abc import ABCConfig, ABCState, run_abc
from repro.core.distributed import make_runner, make_wave_runner
from repro.core.summaries import DISTANCE_KINDS, list_summaries
from repro.epi.data import get_dataset
from repro.epi.models import get_model, list_models
from repro.epi.spec import InterventionSchedule, regionalize
from repro.ioutils import atomic_write_text
from repro.launch.mesh import make_host_mesh


def parse_intervention(spec: str) -> InterventionSchedule | None:
    """Parse an intervention schedule from its CLI string form.

        PARAMS@WINDOW[,WINDOW...]
        PARAMS := name[+name...]            scaled (time-varying) parameters
        WINDOW := day[=SCALES]              new window starting at `day`
        SCALES := entry[+entry...]          one entry, or one per tv param
        entry  := x (pinned scale) | lo:hi (inferred under U(lo, hi))

    A bare `day` infers that window's scales under the default U(0, 2).
    Examples: "alpha@25=0.3" (contact rate pinned to 0.3x from day 25),
    "alpha@25=0.1:1,40" (inferred lockdown window, then a second inferred
    reopening window), "alpha+gamma@30=0.5+0.8".
    """
    spec = (spec or "").strip()
    if not spec or spec.lower() == "none":
        return None
    if "@" not in spec:
        raise ValueError(
            f"intervention {spec!r}: expected PARAMS@day[=scale][,day...]"
        )
    params_s, windows_s = spec.split("@", 1)
    tv_params = tuple(p.strip() for p in params_s.split("+") if p.strip())
    if not tv_params:
        raise ValueError(f"intervention {spec!r}: no parameter names before '@'")
    breakpoints, lows, highs = [], [], []
    for win in windows_s.split(","):
        win = win.strip()
        day_s, _, scales_s = win.partition("=")
        breakpoints.append(int(day_s))
        if not scales_s:
            entries = ["0:2"] * len(tv_params)
        else:
            entries = scales_s.split("+")
            if len(entries) == 1:
                entries = entries * len(tv_params)
        if len(entries) != len(tv_params):
            raise ValueError(
                f"intervention {spec!r}: window {win!r} has {len(entries)} "
                f"scales for {len(tv_params)} parameters"
            )
        lo_row, hi_row = [], []
        for e in entries:
            lo_s, _, hi_s = e.partition(":")
            lo_row.append(float(lo_s))
            hi_row.append(float(hi_s) if hi_s else float(lo_s))
        lows.append(tuple(lo_row))
        highs.append(tuple(hi_row))
    return InterventionSchedule(
        tv_params=tv_params,
        breakpoints=tuple(breakpoints),
        scale_lows=tuple(lows),
        scale_highs=tuple(highs),
    )


def posterior_forecast(
    theta,
    dataset,
    cfg: ABCConfig,
    horizon: int,
    schedule: InterventionSchedule | None = None,
    key=0,
    quantiles=(0.05, 0.25, 0.5, 0.75, 0.95),
    max_particles: int = 512,
) -> dict:
    """Posterior-predictive forecast: simulate accepted particles forward
    past the fitting horizon under a chosen schedule; returns credible bands.

    `theta` is the accepted sample set [N, p]; `schedule` defaults to the
    FIT schedule (cfg.schedule) — pass a different fixed-scale schedule for
    a counterfactual ("what if the lockdown lifts on day 60 instead"). The
    result is a strict-JSON-serializable dict: per observed channel, the
    mean and the requested quantiles over particles for every day of
    `cfg.num_days + horizon`.

    Sets larger than `max_particles` are subsampled with a seeded
    permutation (NOT truncated — topk accepted sets are distance-ordered,
    so taking the first rows would bias the bands toward the lowest-
    distance particles). Delegates to `repro.core.serving.forecast_bands`,
    the same compiled path the `serve --epi` batch server answers from.
    """
    from repro.core.serving import forecast_bands

    return forecast_bands(
        theta,
        dataset,
        model=cfg.model,
        fit_days=cfg.num_days,
        horizon=horizon,
        fit_schedule=cfg.schedule,
        schedule=schedule,
        key=key,
        quantiles=quantiles,
        max_particles=max_particles,
    )


def run_scaling_cli(args):
    """--scaling mode: the paper's multi-device experiment as one command.

    Sweeps the sharded device-resident wave loop over --scaling-devices on
    THIS process's device pool (force host devices on CPU with
    XLA_FLAGS=--xla_force_host_platform_device_count=N) and reports
    parallel_efficiency / scaling_overhead_pct per (model, backend) cell.
    """
    from repro.core.scaling import (
        ScalingConfig,
        format_report,
        run_scaling_study,
    )

    scfg = ScalingConfig(
        device_counts=tuple(args.scaling_devices),
        models=tuple(args.models),
        backends=tuple(args.backends),
        batch_per_device=args.batch,
        waves=args.scaling_waves,
        num_days=args.days,
        dataset=args.dataset,
        reps=args.scaling_reps,
        tile=args.tile,
        scan_unroll=args.scan_unroll,
        autotune=args.autotune,
    )
    report = run_scaling_study(scfg, verbose=True)
    print()
    print(format_report(report))
    if args.scaling_out:
        atomic_write_text(
            args.scaling_out, json.dumps(report, indent=1, allow_nan=False)
        )
        print(f"[scaling] report saved to {args.scaling_out}")
    return report


def run_campaign_cli(args, parser):
    from repro.core.campaign import CampaignConfig, run_campaign

    # the campaign grid reads ONLY the plural flags; refuse the singular ones
    # rather than silently running the wrong grid
    for flag, value in (("--dataset", args.dataset), ("--model", args.model),
                        ("--backend", args.backend), ("--seed", args.seed),
                        ("--intervention", args.intervention),
                        ("--summary", args.summary)):
        if value != parser.get_default(flag.lstrip("-").replace("-", "_")):
            parser.error(
                f"{flag} has no effect with --campaign; use the grid flag "
                f"{flag}s instead"
            )
    models = tuple(args.models)
    if args.regions > 1:
        # regionalize every grid model: the campaign's shape cache keys on
        # the resolved spec object, so spec-object cells behave like names
        models = tuple(
            regionalize(get_model(m), args.regions,
                        args.mobility or "identity")
            for m in models
        )
    cfg = CampaignConfig(
        datasets=tuple(args.datasets),
        models=models,
        backends=tuple(args.backends),
        seeds=tuple(args.seeds),
        interventions=tuple(
            parse_intervention(s) for s in args.interventions
        ),
        summaries=tuple(
            None if s == "identity" else s for s in args.summaries
        ),
        distance=args.distance,
        interpret=_interpret_flag(args.interpret),
        batch_size=args.batch,
        num_days=args.days,
        target_accepted=args.accept,
        max_runs=args.max_runs,
        tolerance=None if args.auto_tolerance else args.tolerance,
        auto_quantile=args.auto_tolerance or 1e-3,
        out_dir=args.out,
        checkpoint_every=args.checkpoint_every,
        devices_per_scenario=args.devices_per_scenario,
        tile=args.tile,
        scan_unroll=args.scan_unroll,
        autotune=args.autotune,
    )
    report = run_campaign(cfg, verbose=True)
    return report


def _interpret_flag(value: str):
    """'auto' -> None (backend-aware), 'on'/'off' -> forced mode."""
    return {"auto": None, "on": True, "off": False}[value]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="synthetic_small")
    ap.add_argument("--model", default="siard", choices=list_models(),
                    help="compartmental model to infer (registry name; the "
                         "paper's SIARD model is the default)")
    ap.add_argument("--regions", type=int, default=1,
                    help="regionalize --model into an N-region spatial "
                         "metapopulation (see repro.epi.spec.regionalize); "
                         "only metapop-aware models (e.g. metapop_seir) "
                         "exchange mass between regions — others become N "
                         "independent copies. 1 = the unchanged model")
    ap.add_argument("--mobility", default="",
                    help="mobility matrix for --regions > 1: 'identity' "
                         "(uncoupled), 'uniform:EPS' or 'ring:EPS' "
                         "(row-stochastic; see repro.epi.spec.make_mobility); "
                         "default identity")
    ap.add_argument("--tolerance", type=float, default=1.6e4,
                    help="absolute epsilon; use --auto-tolerance to calibrate")
    ap.add_argument("--auto-tolerance", type=float, default=0.0, metavar="Q",
                    help="pick epsilon as the Q-quantile of a pilot wave "
                         "(the paper hand-tunes epsilon per dataset)")
    ap.add_argument("--accept", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8192,
                    help="global batch per run (under --scaling: the "
                         "per-DEVICE batch — weak scaling multiplies it by "
                         "the device count)")
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--days", type=int, default=20)
    ap.add_argument("--strategy", default="outfeed", choices=["outfeed", "topk"])
    ap.add_argument("--backend", default="xla_fused",
                    choices=["xla", "xla_fused", "pallas", "npe"])
    ap.add_argument("--npe-steps", type=int, default=None,
                    help="backend=npe: training steps (default NPEConfig)")
    ap.add_argument("--npe-batch", type=int, default=None,
                    help="backend=npe: fresh simulations per training step")
    ap.add_argument("--npe-hidden", type=int, default=None,
                    help="backend=npe: MDN trunk width")
    ap.add_argument("--npe-components", type=int, default=None,
                    help="backend=npe: mixture components")
    ap.add_argument("--summary", default="identity",
                    choices=list(list_summaries()),
                    help="summary statistic compared by --distance (every "
                         "backend lowers every pair; 'identity' is the "
                         "paper's raw daily trajectories)")
    ap.add_argument("--distance", default="euclidean",
                    choices=sorted(DISTANCE_KINDS),
                    help="distance kind over summary values: weighted L2 "
                         "(euclidean), weighted mean-L1 (mae) or observed-"
                         "scale-normalized L2 (normalized_euclidean)")
    ap.add_argument("--interpret", default="auto", choices=["auto", "on", "off"],
                    help="Pallas dispatch for backend=pallas: 'auto' runs the "
                         "interpreter only on CPU and compiled kernels on "
                         "accelerators; 'on'/'off' force a mode")
    ap.add_argument("--tile", type=int, default=None,
                    help="Pallas kernel tile (samples per grid cell); must be "
                         "a multiple of 128 dividing --batch. Default: auto "
                         "(1024-lane legacy default, or the tuning-cache "
                         "winner under --autotune). Pure scheduling — "
                         "accepted sets are identical across tiles")
    ap.add_argument("--scan-unroll", type=int, default=None,
                    help="unroll factor of the xla_fused day scan (pure "
                         "scheduling; default 1, or the tuning-cache winner "
                         "under --autotune)")
    ap.add_argument("--autotune", action="store_true",
                    help="resolve tile/scan-unroll from the measured tuning "
                         "cache under experiments/tuning/ at simulator-build "
                         "time (a cache miss runs the best-of-N search once "
                         "and persists the winners; see repro.core.tuning)")
    ap.add_argument("--intervention", default="",
                    help="piecewise-constant intervention schedule, e.g. "
                         "'alpha@25=0.3' (contact rate pinned to 0.3x from "
                         "day 25) or 'alpha@25=0.1:1' (scale inferred under "
                         "U(0.1, 1)); see parse_intervention for the grammar")
    ap.add_argument("--max-runs", type=int, default=10_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--state", default="", help="checkpoint path (resume if exists)")
    ap.add_argument("--save-posterior", default="")
    ap.add_argument("--multi-device", action="store_true",
                    help="shard_map over all host devices")
    ap.add_argument("--wave-loop", default="auto",
                    choices=["auto", "host", "device"],
                    help="per-wave host sync (host) vs one device-resident "
                         "lax.while_loop over all waves (device)")
    # campaign mode -------------------------------------------------------
    ap.add_argument("--campaign", action="store_true",
                    help="run a dataset x model x backend x seed grid with "
                         "per-scenario checkpoints and one aggregated report")
    ap.add_argument("--datasets", nargs="+",
                    default=["italy", "new_zealand", "usa"],
                    help="campaign dataset grid axis")
    ap.add_argument("--models", nargs="+", default=["siard"],
                    help="campaign model grid axis")
    ap.add_argument("--backends", nargs="+", default=["xla_fused"],
                    help="campaign backend grid axis")
    ap.add_argument("--seeds", nargs="+", type=int, default=[0],
                    help="campaign seed grid axis")
    ap.add_argument("--out", default="experiments/campaigns/default",
                    help="campaign output directory (checkpoints + report)")
    ap.add_argument("--checkpoint-every", type=int, default=32,
                    help="waves per device segment between campaign checkpoints")
    ap.add_argument("--devices-per-scenario", type=int, default=1,
                    help="carve jax.devices() into disjoint groups of this "
                         "size and shard each scenario's wave loop across "
                         "its group (1 = one scenario per device)")
    ap.add_argument("--interventions", nargs="+", default=["none"],
                    help="campaign intervention grid axis (schedule strings; "
                         "'none' is the constant-theta cell). Schedules "
                         "sharing a shape share one compiled wave loop, so "
                         "lockdown-day x scale sweeps never re-trace")
    ap.add_argument("--summaries", nargs="+", default=["identity"],
                    choices=list(list_summaries()),
                    help="campaign summary-statistic grid axis (registry "
                         "names; 'identity' is the raw-trajectory cell)")
    # scaling-study mode ---------------------------------------------------
    ap.add_argument("--scaling", action="store_true",
                    help="run the multi-device scaling study (the paper's "
                         "16-IPU experiment): sharded wave loop at every "
                         "--scaling-devices count, weak scaling with "
                         "--batch per device, efficiency/overhead per "
                         "(model, backend) cell from --models/--backends")
    ap.add_argument("--scaling-devices", nargs="+", type=int,
                    default=[1, 2, 4, 8],
                    help="device counts of the curve (prefix subsets of "
                         "this process's jax.devices())")
    ap.add_argument("--scaling-waves", type=int, default=4,
                    help="fixed wave budget per scaling cell")
    ap.add_argument("--scaling-reps", type=int, default=3,
                    help="timed repetitions per cell (best-of)")
    ap.add_argument("--scaling-out", default="",
                    help="path for the scaling report JSON (default: "
                         "stdout table only; the nightly artifact comes "
                         "from benchmarks/bench_scaling.py)")
    # forecast mode --------------------------------------------------------
    ap.add_argument("--forecast", type=int, default=0, metavar="DAYS",
                    help="after fitting, simulate the accepted particles "
                         "DAYS past the horizon and emit posterior-"
                         "predictive credible bands as strict JSON")
    ap.add_argument("--forecast-schedule", default="",
                    help="counterfactual schedule for the forecast (fixed "
                         "scales only); default: forecast under the FIT "
                         "schedule; 'none': forecast with interventions "
                         "lifted")
    ap.add_argument("--forecast-out", default="",
                    help="path for the forecast JSON (default: stdout)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.regions < 1:
        ap.error("--regions must be >= 1")
    if args.mobility and args.regions == 1:
        ap.error("--mobility has no effect without --regions > 1")
    if args.scaling and (args.regions > 1 or args.mobility):
        ap.error("--regions/--mobility are not supported with --scaling; "
                 "regionalized specs go through single-run or --campaign")

    if "npe" in args.backends and (args.campaign or args.scaling):
        ap.error("backend 'npe' is not a campaign/scaling grid axis (it has "
                 "no wave loop to shard); use the single-run --backend npe")
    if args.backend == "npe":
        if args.multi_device:
            ap.error("--multi-device has no effect with --backend npe: "
                     "training is a single-device jitted loop")
        if args.auto_tolerance:
            ap.error("--auto-tolerance is wave-backend-only; backend npe "
                     "has no tolerance (posterior is a density estimator)")
        if args.state:
            ap.error("--state is wave-backend-only; NPE runs are not "
                     "checkpoint/resumable (re-train or fine-tune instead)")

    if args.campaign:
        return run_campaign_cli(args, ap)
    if args.scaling:
        return run_scaling_cli(args)

    # mirror of run_campaign_cli's guard: grid-only flags do nothing without
    # --campaign — refuse them rather than silently fitting the defaults
    for flag, singular, value in (("--datasets", "--dataset", args.datasets),
                                  ("--models", "--model", args.models),
                                  ("--backends", "--backend", args.backends),
                                  ("--seeds", "--seed", args.seeds),
                                  ("--interventions", "--intervention",
                                   args.interventions),
                                  ("--summaries", "--summary", args.summaries)):
        if value != ap.get_default(flag.lstrip("-").replace("-", "_")):
            ap.error(f"{flag} has no effect without --campaign; use the "
                     f"singular flag {singular} instead")
    for flag, value in (("--scaling-devices", args.scaling_devices),
                        ("--scaling-waves", args.scaling_waves),
                        ("--scaling-reps", args.scaling_reps),
                        ("--scaling-out", args.scaling_out)):
        if value != ap.get_default(flag.lstrip("-").replace("-", "_")):
            ap.error(f"{flag} has no effect without --scaling")

    model = args.model
    if args.regions > 1:
        model = regionalize(
            get_model(args.model), args.regions, args.mobility or "identity"
        )
    ds = get_dataset(args.dataset, num_days=args.days, model=model)
    schedule = parse_intervention(args.intervention)
    interpret = _interpret_flag(args.interpret)
    tolerance = args.tolerance
    if args.auto_tolerance:
        from repro.core.abc import calibrate_tolerance

        pilot_cfg = ABCConfig(batch_size=args.batch, tolerance=1.0,
                              num_days=args.days, backend=args.backend,
                              strategy="topk", top_k=1, model=model,
                              schedule=schedule, interpret=interpret,
                              summary=args.summary, distance=args.distance)
        tolerance = calibrate_tolerance(ds, pilot_cfg, key=args.seed,
                                        quantile=args.auto_tolerance)
        print(f"[abc] auto-calibrated tolerance = {tolerance:.4g} "
              f"(quantile {args.auto_tolerance:g})")
    npe_overrides = {
        k: v for k, v in (("train_steps", args.npe_steps),
                          ("train_batch", args.npe_batch),
                          ("hidden", args.npe_hidden),
                          ("n_components", args.npe_components))
        if v is not None
    }
    if npe_overrides and args.backend != "npe":
        ap.error("--npe-* flags have no effect without --backend npe")
    npe_cfg = None
    if npe_overrides:
        from repro.core.npe import NPEConfig

        npe_cfg = NPEConfig(**npe_overrides)
    cfg = ABCConfig(
        batch_size=args.batch,
        tolerance=tolerance,
        target_accepted=args.accept,
        strategy=args.strategy,
        chunk_size=args.chunk,
        num_days=args.days,
        backend=args.backend,
        max_runs=args.max_runs,
        model=model,
        wave_loop=args.wave_loop,
        schedule=schedule,
        interpret=interpret,
        summary=args.summary,
        distance=args.distance,
        tile=args.tile,
        scan_unroll=args.scan_unroll,
        autotune=args.autotune,
        npe=npe_cfg,
    )
    run_fn = None
    wave_runner = None
    if args.multi_device:
        mesh = make_host_mesh(model=1)
        if args.wave_loop == "device":
            wave_runner = make_wave_runner(mesh, ds, cfg)
        else:
            run_fn = make_runner(mesh, ds, cfg)

    state = None
    if args.state:
        import os

        if os.path.exists(args.state):
            state = ABCState.load(args.state)
            print(f"[abc] resuming from run {state.run_idx} "
                  f"({state.n_accepted} accepted)")

    post = run_abc(
        ds, cfg, key=args.seed, state=state, run_fn=run_fn,
        wave_runner=wave_runner,
        checkpoint_every=25 if args.state else 0,
        checkpoint_path=args.state or None, verbose=True,
    )
    print(post.summary_table())
    if args.save_posterior:
        post.save(args.save_posterior)
        print(f"[abc] posterior saved to {args.save_posterior}")
    if args.forecast:
        from repro.epi.spec import EMPTY_SCHEDULE

        if args.forecast_schedule:
            # an explicit counterfactual; "none" lifts every intervention
            fc_sched = parse_intervention(args.forecast_schedule) or EMPTY_SCHEDULE
        else:
            fc_sched = None  # forecast under the fit schedule
        bands = posterior_forecast(
            post.theta, ds, cfg, args.forecast, schedule=fc_sched,
            key=args.seed + 1,
        )
        text = json.dumps(bands, indent=1, allow_nan=False)
        if args.forecast_out:
            atomic_write_text(args.forecast_out, text)
            print(f"[abc] forecast bands saved to {args.forecast_out}")
        else:
            print(text)
    return post


if __name__ == "__main__":
    main()
