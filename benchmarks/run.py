"""Benchmark aggregator: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # quick (CPU-minutes)
    PYTHONPATH=src python -m benchmarks.run --full
    PYTHONPATH=src python -m benchmarks.run --only table1,roofline
"""

from __future__ import annotations

import argparse
import shutil
import sys
import time
import traceback
from pathlib import Path

from repro.compile_cache import enable_compile_cache

BENCHES = {
    "table1": ("benchmarks.bench_runtime", "Table 1: runtime vs tolerance/accepted"),
    "table2_3": ("benchmarks.bench_batch_sweep", "Tables 2-3: batch-size sweep"),
    "table4": ("benchmarks.bench_postproc", "Table 4: host postprocessing"),
    "fig6": ("benchmarks.bench_tolerance_curve", "Fig 6: tolerance curve"),
    "table7": ("benchmarks.bench_scaling", "Table 7: device scaling"),
    "table8": ("benchmarks.bench_countries", "Table 8: three countries"),
    "abc_perf": ("benchmarks.bench_abc_perf", "ABC backend perf + 512-chip dry-run"),
    "roofline": ("benchmarks.roofline", "Roofline aggregation"),
}


#: the gate-compatible artifacts with committed baselines: (module, argv).
#: `--refresh` reruns exactly these and copies the fresh JSON over
#: experiments/bench/baselines/ in one command (the re-baselining friction
#: cutter named by the ROADMAP; commit the result in a reviewed change).
BASELINED = {
    "wave_loop.json": ("benchmarks.bench_wave_loop", []),
    "campaign.json": ("benchmarks.bench_campaign", []),
    "scaling.json": ("benchmarks.bench_scaling", []),
}


def refresh_baselines() -> int:
    import importlib

    bench_dir = Path(__file__).resolve().parents[1] / "experiments" / "bench"
    baseline_dir = bench_dir / "baselines"
    baseline_dir.mkdir(parents=True, exist_ok=True)
    failures = []
    for name, (module, argv) in BASELINED.items():
        print(f"\n{'='*72}\n[refresh] {module} -> {name}\n{'='*72}",
              flush=True)
        try:
            importlib.import_module(module).main(list(argv))
        except Exception:
            failures.append(name)
            traceback.print_exc()
            continue
        fresh = bench_dir / name
        if not fresh.exists():
            failures.append(name)
            print(f"[refresh] {module} produced no {fresh}")
            continue
        shutil.copyfile(fresh, baseline_dir / name)
        print(f"[refresh] baselined {baseline_dir / name}")
    if failures:
        print(f"[refresh] FAILED for: {failures}")
        return 1
    print(f"\n[refresh] all baselines regenerated under {baseline_dir}; "
          "review + commit them (tests/check_bench_regression.py gates "
          "against this set)")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--refresh", action="store_true",
                    help="regenerate every experiments/bench/baselines/*.json "
                         "in one command (runs the baselined benchmarks with "
                         "their default settings, then copies the fresh "
                         "artifacts over the baselines)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.refresh:
        sys.exit(refresh_baselines())
    only = set(args.only.split(",")) if args.only else set(BENCHES)

    failures = []
    t0 = time.time()
    for key, (module, desc) in BENCHES.items():
        if key not in only:
            continue
        print(f"\n{'='*72}\n[bench:{key}] {desc}\n{'='*72}", flush=True)
        try:
            mod = __import__(module, fromlist=["run"])
            t = time.time()
            mod.run(quick=not args.full)
            print(f"[bench:{key}] done in {time.time()-t:.1f}s", flush=True)
        except Exception:
            failures.append(key)
            traceback.print_exc()
    print(f"\n{'='*72}\nbenchmarks finished in {time.time()-t0:.1f}s; "
          f"failures: {failures or 'none'}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
