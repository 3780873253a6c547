"""Readings that the limits in `limits/<cell>.json` are set from.

    python3 bench/readings.py --workload siard3.deep --seeds 12 \\
        --control-seeds 3 --seconds 4 --out chiprun_out/readings.json

In one process, after one set-up: the program's numbers on `--seeds`
seeds (a short window each, at the cell's own load, checked as a run
checks it: the lower readings) and the numbers of the reference computed
in bfloat16 and put in the program's place on `--control-seeds` seeds
(the upper readings). Prints each number's largest program reading and
smallest control reading; writes every reading to `--out`.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))

    import jax
    import jax.numpy as jnp

    from bench import harness
    from bench.run import chips_for
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(harness.load_benchmark(), args.workload)
    devices = chips_for(cell)
    program = harness.ProgramFits(cell, devices)
    ref_eps = harness.reference_epsilons(cell, program.datasets, devices)
    n = len(program.datasets)
    out = {"workload": args.workload, "program": [], "control": [],
           "epsilons": program.epsilons, "reference_epsilons": ref_eps}
    for k in range(args.seeds):
        seed = args.first_seed + k
        t0 = time.perf_counter()
        win = harness.run_window(program, cell, seed, args.seconds, 0.0)
        nums = harness.check(cell, program.datasets, program.epsilons,
                             win.fits, seed, ref_eps, devices)
        waves = sorted(f.waves for f in win.fits)
        out["program"].append({"seed": seed, "numbers": nums,
                               "fits": len(win.fits), "waves": waves,
                               "dataset_waves": [[f.dataset, f.waves]
                                                 for f in win.fits]})
        print(f"[program] seed={seed} fits={len(win.fits)} "
              f"waves_median={waves[len(waves) // 2]} {nums} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)
    control = harness.ControlFits(cell, program.datasets, jnp.bfloat16,
                                  devices)
    check_fits = int(cell.traffic["check_fits"])
    for k in range(args.control_seeds):
        seed = args.first_seed + 1000 + k
        key = harness.seed_key(seed)
        fits = [control.fit(i, i % n, jax.random.fold_in(key, i))
                for i in range(check_fits)]
        nums = harness.check(cell, program.datasets, control.epsilons, fits,
                             seed, ref_eps, devices)
        out["control"].append({"seed": seed, "numbers": nums})
        print(f"[control] seed={seed} {nums}", flush=True)
    lower = {k: max(r["numbers"][k] for r in out["program"])
             for k in out["program"][0]["numbers"]} if out["program"] else {}
    upper = {k: min(r["numbers"][k] for r in out["control"])
             for k in out["control"][0]["numbers"]} if out["control"] else {}
    out["lower"], out["upper"] = lower, upper
    print(f"[lower] {lower}\n[upper] {upper}", flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
