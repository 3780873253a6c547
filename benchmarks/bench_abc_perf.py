"""§Perf for the paper's own workload: paper-faithful baseline vs fused vs
Pallas-kernel ABC, plus the 512-chip dry-run of the sharded ABC step.

Measured on CPU (wall time, real): "xla" (paper-faithful full [B,3,T]
trajectory + separate distance) vs "xla_fused" (running distance, no
trajectory). The Pallas path is validated in interpret mode (correctness) and
projected with the mandated v5e constants via its analytic traffic model —
interpret-mode wall time is meaningless and never reported as performance.
"""

from __future__ import annotations

import os
import subprocess
import sys

import jax

from benchmarks.common import render_table, save_result, time_fn
from benchmarks.roofline import abc_kernel_roofline
from repro.core.abc import ABCConfig, abc_run_batch, make_simulator
from repro.core.priors import paper_prior
from repro.epi.data import get_dataset
from repro.launch.analysis import analyze_hlo

DAYS = 49  # full paper horizon for this one
BATCH = 16384

_DRYRUN_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import jax, json
from repro.core.abc import ABCConfig, make_simulator
from repro.core.distributed import make_shardmap_runner
from repro.core.priors import paper_prior
from repro.epi.data import get_dataset
from repro.launch.analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh

for multi in (False, True):
    mesh = make_production_mesh(multi_pod=multi)
    n = mesh.size
    ds = get_dataset("italy", num_days=49)
    cfg = ABCConfig(batch_size=100_000 * n, tolerance=5e4, target_accepted=10**9,
                    chunk_size=10_000, num_days=49, backend="xla_fused",
                    max_runs=1)
    runner = make_shardmap_runner(mesh, paper_prior(), make_simulator(ds, cfg), cfg)
    lowered = runner.lower(jax.random.PRNGKey(0))
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    costs = analyze_hlo(compiled.as_text())
    print("DRYRUN", json.dumps({
        "mesh": "2x16x16" if multi else "16x16",
        "devices": n,
        "global_batch": cfg.batch_size,
        "peak_hbm_bytes": mem.argument_size_in_bytes + mem.output_size_in_bytes
                          + mem.temp_size_in_bytes - mem.alias_size_in_bytes,
        "collective_wire_bytes": costs.total_wire,
        "collective_detail": {k: float(v) for k, v in costs.collective_wire.items()},
        "bytes_per_device": costs.bytes_accessed,
    }))
"""


def run(quick: bool = True):
    ds = get_dataset("italy", num_days=DAYS)
    rows, raw = [], {}
    # --- measured: paper-faithful vs fused (real CPU wall time) ---
    for backend in ("xla", "xla_fused"):
        cfg = ABCConfig(batch_size=BATCH, tolerance=5e4, target_accepted=10**9,
                        chunk_size=2048, num_days=DAYS, backend=backend, max_runs=1)
        run_fn = jax.jit(abc_run_batch(paper_prior(), make_simulator(ds, cfg), cfg))
        costs = analyze_hlo(run_fn.lower(jax.random.PRNGKey(0)).compile().as_text())
        t = time_fn(lambda k=jax.random.PRNGKey(1): run_fn(k), iters=5)
        rows.append([backend, f"{t['p50_s']*1e3:.1f}",
                     f"{costs.bytes_accessed/1e6:.0f}",
                     f"{costs.bytes_accessed/BATCH:.0f}"])
        raw[backend] = {"ms_per_run": t["p50_s"] * 1e3,
                        "bytes_accessed": costs.bytes_accessed,
                        "bytes_per_sample": costs.bytes_accessed / BATCH}
    # --- pallas kernel: correctness already covered by tests; analytic roofline
    roof = abc_kernel_roofline(batch=100_000, days=DAYS)
    raw["pallas_analytic"] = roof

    # --- kernel tile sweep: VMEM working set per grid cell (structural knob;
    # correctness across tiles is asserted in tests/test_kernel_abc_sim.py).
    # Working set = theta(8xTB) + state(7xTB incl. acc) + ~10 live temps, f32.
    tile_rows = []
    for tile in (256, 512, 1024, 2048, 4096, 8192):
        vmem_kb = (8 + 7 + 10) * tile * 4 / 1024
        cells_in_vmem = int(16 * 1024 // max(vmem_kb, 1))
        tile_rows.append([tile, f"{vmem_kb:.0f}", cells_in_vmem])
        raw[f"tile_{tile}"] = {"vmem_kb": vmem_kb}
    print("\n== Pallas kernel tile sweep (VMEM per grid cell, 16MB budget) ==")
    print(render_table(["tile (samples)", "VMEM KB", "concurrent cells"], tile_rows))
    print("choice: tile=1024 (default) keeps ~100 KB/cell — deep multi-cell "
          "pipelining headroom while staying lane-aligned (8 x 128)")
    print("\n== ABC backends (batch 16384 x 49 days, measured on CPU) ==")
    print(render_table(["backend", "ms/run", "MB accessed", "B/sample"], rows))
    speed = raw["xla"]["ms_per_run"] / raw["xla_fused"]["ms_per_run"]
    mem_cut = raw["xla"]["bytes_per_sample"] / raw["xla_fused"]["bytes_per_sample"]
    print(f"fused vs paper-faithful: {speed:.2f}x wall, {mem_cut:.2f}x less traffic")
    print(f"pallas kernel (projected, v5e): AI fused={roof['arithmetic_intensity_fused']:.0f} "
          f"vs naive={roof['arithmetic_intensity_naive']:.1f} flops/B; "
          f"t_mem fused={roof['t_memory_fused_s']*1e6:.1f}us vs naive={roof['t_memory_naive_s']*1e6:.0f}us per run")

    # --- 512-chip dry run of the sharded ABC step ---
    if not quick or os.environ.get("REPRO_ABC_DRYRUN", "1") == "1":
        env = dict(os.environ)
        env["PYTHONPATH"] = "src:."
        # the dry run is 512 forced host devices: keep the child off any
        # accelerator this process already holds
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, "-c", _DRYRUN_CODE], env=env, capture_output=True,
            text=True, timeout=900,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert out.returncode == 0, out.stderr[-2000:]
        import json as _json

        for line in out.stdout.splitlines():
            if line.startswith("DRYRUN"):
                rec = _json.loads(line[len("DRYRUN "):])
                raw[f"dryrun_{rec['mesh']}"] = rec
                print(f"ABC dry-run {rec['mesh']}: {rec['devices']} chips, "
                      f"global batch {rec['global_batch']:,}, "
                      f"hbm/dev {rec['peak_hbm_bytes']/2**20:.0f} MiB, "
                      f"collective wire {rec['collective_wire_bytes']/1e3:.1f} KB "
                      f"({rec['collective_detail']})")
    save_result("abc_perf", raw)
    return raw


if __name__ == "__main__":
    run()
