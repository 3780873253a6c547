"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload siard3.deep --seed 7 --seconds 30 --trace 0

Set-up (`setup_s`) runs from the moment JAX has found the chips to the
first timed fit: the program's imports, datasets, eps pilots, compilation
or a load from the persistent cache in `.jax_cache/`, and one warm-up fit
per dataset. Starting Python, importing JAX and starting the TPU runtime
come before it and are printed apart (`runtime_s`). The window then runs
fits back to back for `--seconds`. With `--trace 0` the last line of
standard output reports the cell's end-to-end metrics, with `--trace 1`
its per-layer metrics from a profiler trace of the window's first
`TRACED_SECONDS`. After the window, a plain reference re-derives a sample
of the fits (`compare.py`); every number it compares is printed beside its
limit as the last lines of standard error and under `checks` in the
result.

The run fails, and prints no result, where JAX finds no TPU, fewer chips
than the cell asks for, or a chip that `peaks.json` does not list.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
#: a `--trace 1` run traces the first seconds of its window, a few seconds
#: of fits: the trace of a whole window on four chips takes longer to
#: collect and read than a run may last
TRACED_SECONDS = 5.0


class NoChip(RuntimeError):
    pass


def chips_for(cell, platform: str = "tpu"):
    """The devices the cell runs on; raises NoChip where they are missing."""
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        raise NoChip(f"JAX found {devices[0].platform}, not a {platform}")
    if len(devices) < cell.chips:
        raise NoChip(f"cell {cell.name} needs {cell.chips} chips, JAX found "
                     f"{len(devices)}")
    with open(ROOT / "bench" / "peaks.json") as f:
        peaks = json.load(f)["devices"]
    if platform == "tpu" and devices[0].device_kind not in peaks:
        raise NoChip(f"{devices[0].device_kind!r} is not in bench/peaks.json")
    return devices[: cell.chips]


class TraceContext:
    """What a per-layer reader sees: the reduced trace of the window."""

    def __init__(self, trace, devices, window, wave_module):
        from bench import tracing

        self.trace = trace
        self.t0, self.t1 = tracing.window(trace)
        self.planes = tracing.device_planes(trace)[: len(devices)]
        self.wave_module = wave_module
        self.fits = {f.index: f for f in window.fits}

    def fit_spans(self):
        from bench import tracing

        return [s for s in tracing.spans(self.trace, "bench.fit")
                if self.t0 <= s[0] and s[1] <= self.t1]

    def traced_waves(self) -> int:
        return sum(self.fits[int(s[2]["fit"])].waves
                   for s in self.fit_spans() if int(s[2]["fit"]) in self.fits)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run(workload: str, seed: int, seconds: float, trace: bool,
        platform: str = "tpu", control=None) -> dict:
    """One run of a cell; returns the result line's object. `platform` and
    `control` (a dtype: the reference in that precision in the program's
    place) serve the tests and the limit readings."""
    import jax

    from bench import compare, harness

    bench = harness.load_benchmark()
    cell = harness.load_cell(bench, workload)
    devices = chips_for(cell, platform)
    t_setup = time.perf_counter()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    program = harness.ProgramFits(cell, devices)
    fits_of = (program if control is None else
               harness.ControlFits(cell, program.datasets, control, devices))
    setup_s = time.perf_counter() - t_setup
    print(f"[setup] runtime_s={t_setup - T_START:.3f} "
          + " ".join(f"{k}_s={v:.3f}" for k, v in program.phases.items())
          + f" setup_s={setup_s:.3f}", flush=True)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        # Python's own calls are not traced: that tracer costs the host
        # more than the fit driver it would describe
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    window = harness.run_window(
        fits_of, cell, seed, seconds, setup_s,
        end_trace=jax.profiler.stop_trace if trace else None,
        traced_seconds=TRACED_SECONDS)
    slow = sorted(window.fits, key=lambda f: -f.latency)[:3]
    ends = [f.start + f.latency for f in window.fits]
    between = max((b.start - a for a, b in zip(ends, window.fits[1:])),
                  default=0.0)
    print(f"[window] fits={len(window.fits)} seconds={window.seconds:.3f} "
          f"compiles_in_window={window.compiles} slowest_fits="
          + ",".join(f"{f.index}:{f.latency:.4f}" for f in slow)
          + f" longest_between_fits_s={between:.4f}", flush=True)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak(devices)}
    result = {"correct": False, "attempted": len(window.fits),
              "failed": 0, "metrics": {}, "device": device}
    source, kind = window, ("end_to_end", "e2e")
    if trace:
        from bench import tracing

        try:
            reduced = tracing.load_xplane(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        source = ctx = TraceContext(reduced, devices, window,
                                    program.wave_module)
        kind = ("per_layer", "layers")
        busy = [tracing.covered(tracing.busy(p), ctx.t0, ctx.t1)
                for p in ctx.planes]
        device["busy_s"] = sum(busy) / max(1, len(busy)) / 1e9
        device["window_s"] = (ctx.t1 - ctx.t0) / 1e9
        result["breakdown"] = tracing.breakdown(
            {"planes": ctx.planes + [p for p in reduced["planes"]
                                     if p["name"].startswith("/host:")]},
            ctx.t0, ctx.t1)
    for m in harness.metrics_of(bench, workload, kind[0]):
        value = harness.reader(kind[1], m["name"])(source)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}

    datasets, epsilons = program.datasets, fits_of.epsilons
    del program, fits_of
    numbers = harness.check(cell, datasets, epsilons, window.fits, seed,
                            devices=devices)
    ok, table = compare.judge(numbers, cell.limits)
    result["correct"] = ok
    result["failed"] = int(numbers.get("short_fits", 0))
    result["window_compiles"] = window.compiles
    result["checks"] = table
    for name, row in table.items():
        print(f"[check] {name} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    try:
        run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
