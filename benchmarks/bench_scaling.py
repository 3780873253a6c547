"""Throughput-vs-device-count scaling benchmark (the paper's 16-IPU figure).

    PYTHONPATH=src python benchmarks/bench_scaling.py \
        [--devices 1 2 4 8] [--batch-per-device 2048] [--waves 4] \
        [--models siard] [--backends xla_fused]

Runs `repro.core.scaling.run_scaling_study`: the sharded device-resident
wave loop (`distributed.make_wave_runner`, collective stop via psum,
per-shard accept buffers gathered at host re-entry) over a fixed wave
budget at every device count, under weak scaling (global batch = n *
batch_per_device — the paper's "2x100k means 100k per IPU"). Every
(model, backend, batch, n) cell records `parallel_efficiency` and
`scaling_overhead_pct`, the reproduction's analogue of the paper's <= 8%
overhead claim at 16 IPUs.

On a CPU run (`JAX_PLATFORMS=cpu`) the script re-execs itself once with
`--xla_force_host_platform_device_count` set, so
the nightly job measures the structural overhead curve on simulated host
devices (the wall-clock cannot speed up on one physical core; efficiency
there tracks dispatch + collective overhead, which is exactly what the
regression gate pins). The JSON artifact is gate-compatible
(bench-artifact/v1): per-cell wall clocks gated at +25%, simulation counts
gated as parity.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _harness import emit_artifact, roofline_fields  # noqa: E402

_CHILD_ENV = "_BENCH_SCALING_CHILD"


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", nargs="+", type=int, default=[1, 2, 4, 8],
                    help="device counts of the curve (prefix subsets of the "
                         "visible device pool)")
    ap.add_argument("--batch-per-device", type=int, default=2048,
                    help="per-DEVICE batch (weak scaling: the n-device cell "
                         "simulates n x this per wave)")
    ap.add_argument("--waves", type=int, default=4,
                    help="fixed wave budget per cell (acceptance target is "
                         "unreachable, so every cell burns exactly this)")
    ap.add_argument("--models", nargs="+", default=["siard"])
    ap.add_argument("--backends", nargs="+", default=["xla_fused"])
    ap.add_argument("--days", type=int, default=20)
    ap.add_argument("--dataset", default="synthetic_small")
    ap.add_argument("--reps", type=int, default=3,
                    help="timed repetitions per cell (best-of; warmup "
                         "excluded)")
    ap.add_argument("--out-name", default="scaling",
                    help="artifact basename under experiments/bench/")
    return ap.parse_args(argv)


def _ensure_devices(need: int, argv) -> int | None:
    """Re-exec once with `need` forced host devices on a CPU run.

    Decided from the environment alone, before this process touches JAX:
    a parent that had initialised a backend would hold the chip its child
    needs. Returns the child's exit code, or None to run in this process
    (accelerators, a caller-set device count, or already the child).
    """
    if (
        os.environ.get(_CHILD_ENV)
        or os.environ.get("JAX_PLATFORMS") != "cpu"
        or "xla_force_host_platform_device_count" in os.environ.get(
            "XLA_FLAGS", "")
    ):
        return None
    env = dict(os.environ)
    env[_CHILD_ENV] = "1"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={need}"
    ).strip()
    print(f"[bench_scaling] re-exec with {need} simulated host devices")
    return subprocess.run(
        [sys.executable, os.path.abspath(__file__), *argv], env=env
    ).returncode


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    args = parse_args(argv)
    child_rc = _ensure_devices(max(args.devices), argv)
    if child_rc is not None:
        if child_rc:
            raise SystemExit(child_rc)
        return None  # the child produced the artifact

    from repro.core.scaling import (
        ScalingConfig,
        format_report,
        run_scaling_study,
    )

    scfg = ScalingConfig(
        device_counts=tuple(args.devices),
        models=tuple(args.models),
        backends=tuple(args.backends),
        batch_per_device=args.batch_per_device,
        waves=args.waves,
        num_days=args.days,
        dataset=args.dataset,
        reps=args.reps,
    )
    report = run_scaling_study(scfg, verbose=True)
    print()
    print(format_report(report))

    cells, parity = {}, {}
    for key, cell in report["cells"].items():
        cells[key] = {
            "wall_s": cell["wall_s"],
            "sims_per_s": cell["sims_per_s"],
            "parallel_efficiency": cell["parallel_efficiency"],
            "scaling_overhead_pct": cell["scaling_overhead_pct"],
            "devices": cell["devices"],
            "global_batch": cell["global_batch"],
            **roofline_fields(cell["model"], args.days,
                              cell["simulations"], cell["wall_s"]),
        }
        # the wave budget is fixed, so per-cell simulation counts (and the
        # device counts themselves) are deterministic parity metrics
        parity[key] = {
            "simulations": cell["simulations"],
            "devices": cell["devices"],
            "waves": cell["waves"],
        }
    path = emit_artifact(
        Path(args.out_name).name,
        cells=cells,
        parity=parity,
        meta={k: v for k, v in report["config"].items()},
        extra={"report": report},
    )
    print(f"\nsaved {path}")
    return report


def run(quick: bool = True):
    """`benchmarks.run` aggregator entry (the paper's Table 7 slot)."""
    argv = (
        ["--devices", "1", "2", "4", "--batch-per-device", "512",
         "--waves", "2", "--reps", "1", "--days", "15",
         "--out-name", "scaling_quick"]
        if quick
        else ["--devices", "1", "2", "4", "8"]
    )
    return main(argv)


if __name__ == "__main__":
    main()
