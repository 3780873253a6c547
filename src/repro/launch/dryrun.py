import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry run: lower + compile every (architecture x input-shape x mesh)
cell on the production meshes; record memory/cost/collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --arch all --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-27b --shape train_4k

Results are cached as JSON under experiments/dryrun/ (one file per cell) and
aggregated by benchmarks/roofline.py into EXPERIMENTS.md tables. The 512
placeholder-device forcing above MUST precede any jax import (device count
locks on first init) and lives ONLY here, per the dry-run contract.
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax  # locks the forced device count before other imports

from repro.ioutils import atomic_write_text
from repro.launch.analysis import roofline_from_compiled
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, SHAPE_ORDER, applicable
from repro.launch.steps import build_step
from repro.models.registry import get_model, list_archs

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def run_cell(arch: str, shape_name: str, multi_pod: bool, rules_overrides=None,
             tag: str = "baseline", **step_kwargs) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    model = get_model(arch)
    shape = SHAPES[shape_name]
    if shape.mode != "train":
        step_kwargs.pop("microbatch", None)
    t0 = time.time()
    with jax.set_mesh(mesh):
        built = build_step(model, mesh, shape, rules_overrides=rules_overrides,
                           **step_kwargs)
        lowered = built.fn.lower(*built.arg_shapes)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        print(f"  memory_analysis[{arch}/{shape_name}]: {mem}")  # proves it fits
        print(f"  cost_analysis[{arch}/{shape_name}]: "
              f"{ {k: v for k, v in (compiled.cost_analysis() or {}).items() if k in ('flops', 'bytes accessed')} }")
        roof = roofline_from_compiled(compiled, model, shape, n_dev)
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "tag": tag,
        "mode": shape.mode,
        "n_devices": n_dev,
        "status": "ok",
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_hbm_bytes": mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "roofline": roof.to_dict(),
        "param_count": model.param_count(),
        "active_param_count": model.active_param_count(),
    }
    return rec


def cell_path(arch, shape_name, multi_pod, tag="baseline") -> Path:
    mesh = "multi" if multi_pod else "single"
    return OUT_DIR / f"{arch}__{shape_name}__{mesh}__{tag}.json"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--microbatch", type=int, default=1)
    args = ap.parse_args()

    archs = list(list_archs()) if args.arch == "all" else [args.arch]
    shapes = list(SHAPE_ORDER) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    OUT_DIR.mkdir(parents=True, exist_ok=True)

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        model = get_model(arch)
        for shape_name in shapes:
            if not applicable(model, shape_name):
                print(f"SKIP  {arch} x {shape_name} (long_500k needs sub-quadratic; "
                      f"see DESIGN.md §Arch-applicability)")
                n_skip += 1
                continue
            for multi_pod in meshes:
                path = cell_path(arch, shape_name, multi_pod, args.tag)
                if path.exists() and not args.force:
                    print(f"CACHED {path.name}")
                    n_ok += 1
                    continue
                label = f"{arch} x {shape_name} x {'2x16x16' if multi_pod else '16x16'}"
                try:
                    rec = run_cell(arch, shape_name, multi_pod, tag=args.tag,
                                   microbatch=args.microbatch)
                    atomic_write_text(path, json.dumps(rec, indent=1))
                    r = rec["roofline"]
                    print(
                        f"OK    {label}: compile={rec['compile_s']:.0f}s "
                        f"hbm/dev={rec['memory']['peak_hbm_bytes']/2**30:.2f}GiB "
                        f"t_comp={r['t_compute_s']:.2e} t_mem={r['t_memory_s']:.2e} "
                        f"t_coll={r['t_collective_s']:.2e} -> {r['bottleneck']}"
                        , flush=True,
                    )
                    n_ok += 1
                except Exception as e:
                    n_fail += 1
                    err = {"arch": arch, "shape": shape_name,
                           "mesh": "2x16x16" if multi_pod else "16x16",
                           "status": "fail", "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-3000:]}
                    atomic_write_text(
                        path.with_suffix(".fail.json"), json.dumps(err, indent=1)
                    )
                    print(f"FAIL  {label}: {type(e).__name__}: {str(e)[:300]}", flush=True)
    print(f"\ndry-run complete: ok={n_ok} skip={n_skip} fail={n_fail}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
