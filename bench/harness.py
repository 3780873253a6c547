"""The benchmark's cells, their set-up, the measured window and the check.

Everything that belongs to one configuration, traffic mix or metric sits in
a file of its own, found by the name that `BENCHMARK.json` gives:

  configs/<config>.json      sizes of the deployment, and its source
  configs/<reference>        its plain reference model, named in the config
  traffic/<traffic>.json     parameters of the mix
  limits/<cell>.json         the limit of each number the check compares
  e2e/<metric>.py            `read(window)` of an end-to-end metric
  layers/<metric>.py         `read(trace context)` of a per-layer metric

A cell runs the program's fits the way a user does: one wave runner per
dataset, built and warmed in set-up, then `run_abc` back to back from one
client, fit i on dataset i mod n with key fold_in(seed, i).
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np
from jax import monitoring

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: fixed keys of the tolerance pilot and of the warm-up fits, so that the
#: work of set-up and of every fit is the same whatever `--seed` is
PILOT_KEY = 2012
WARM_KEY = 14332


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    limits: dict

    @property
    def wave_size(self) -> int:
        return int(self.config["batch_per_chip"]) * self.chips

    @property
    def quantile(self) -> float:
        """eps quantile that makes a fit take `design_waves_per_fit` waves."""
        return (self.config["target_accepted"]
                / (self.traffic["design_waves_per_fit"] * self.wave_size))

    @property
    def n_pilot(self) -> int:
        """Pilot simulations, whole waves, that put eps at about the
        `pilot_rank`-th smallest pilot distance."""
        want = self.traffic["pilot_rank"] / self.quantile
        return max(1, math.ceil(want / self.wave_size - 1e-9)) * self.wave_size

    @property
    def max_check_waves(self) -> int:
        """Waves of one fit the reference follows at most."""
        return math.ceil(3 * self.traffic["design_waves_per_fit"]) + 10


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return _json(path)


def load_cell(bench: dict, name: str) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    return Cell(
        name=name,
        config=_json(BENCH / "configs" / f"{w['config']}.json"),
        traffic=_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        chips=int(w["chips"]),
        limits=_json(BENCH / "limits" / f"{name}.json"),
    )


def metrics_of(bench: dict, cell: str, kind: str) -> list[dict]:
    """The `end_to_end` or `per_layer` entries that this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def reader(kind: str, name: str) -> Callable:
    """`read` of `e2e/<name>.py` or `layers/<name>.py`."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def seed_key(seed: int):
    """A key for any whole seed: PRNGKey keeps 32 bits, so the higher ones
    are folded in."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


# ------------------------------------------------------------------ the fits

@dataclasses.dataclass
class Fit:
    index: int
    dataset: int
    key: object
    start: float
    latency: float
    waves: int
    simulations: int
    theta: np.ndarray
    distances: np.ndarray
    epsilon: float


class ProgramFits:
    """The system under test: datasets, eps pilots and one warmed wave
    runner per dataset, then `run_abc` through them."""

    def __init__(self, cell: Cell, devices):
        clock = time.perf_counter()
        self.phases: dict[str, float] = {}

        def phase(name):
            nonlocal clock
            now = time.perf_counter()
            self.phases[name] = self.phases.get(name, 0.0) + now - clock
            clock = now

        import dataclasses as dc

        import jax

        from repro.core import abc, distributed
        from repro.epi.data import get_dataset
        from repro.epi.models import get_model
        from repro.epi.spec import regionalize

        phase("imports")
        c = cell.config
        spec = get_model(c["model"])
        if c["regions"] > 1:
            spec = regionalize(spec, c["regions"], c["mobility"])
        self.cell = cell
        self.datasets = [get_dataset(n, num_days=c["num_days"], model=spec)
                         for n in c["datasets"]]
        phase("datasets")
        base = abc.ABCConfig(
            batch_size=cell.wave_size, target_accepted=c["target_accepted"],
            num_days=c["num_days"], model=spec, distance=c["distance"])
        self.cfgs, self.runners, self.epsilons = [], [], []
        for ds in self.datasets:
            eps = abc.calibrate_tolerance(
                ds, base, key=PILOT_KEY, quantile=cell.quantile,
                n_pilot=cell.n_pilot)
            phase("pilots")
            cfg = dc.replace(base, tolerance=eps)
            if cell.chips == 1:
                runner = abc.make_wave_runner(
                    spec.prior(), abc.make_simulator(ds, cfg), cfg)
            else:
                mesh = jax.sharding.Mesh(np.asarray(devices), ("data",))
                runner = distributed.make_wave_runner(
                    mesh, ds, cfg, style="shard_map")
            self.cfgs.append(cfg)
            self.runners.append(runner)
            self.epsilons.append(eps)
        self.wave_module = "jit_" + self.runners[0].fn.__name__
        phase("runners")
        for d in range(len(self.datasets)):
            self.fit(-1 - d, d, jax.random.PRNGKey(WARM_KEY + d))
        # set-up's garbage goes now, and what it keeps is not scanned again
        # by the collections that the window's own garbage sets off
        gc.collect()
        gc.freeze()
        phase("warm_up_fits")

    def fit(self, index: int, d: int, key) -> Fit:
        from repro.core.abc import run_abc

        t0 = time.perf_counter()
        post = run_abc(self.datasets[d], self.cfgs[d], key=key,
                       wave_runner=self.runners[d])
        latency = time.perf_counter() - t0
        return Fit(index, d, key, t0, latency, int(post.runs),
                   int(post.simulations), post.theta, post.distances,
                   self.epsilons[d])


class ControlFits:
    """The reference in a lower precision, put in the program's place."""

    def __init__(self, cell: Cell, datasets, dtype, devices=None):
        from bench import reference

        self.cell = cell
        self.refs = [reference.Reference(cell.config, ds, dtype, devices)
                     for ds in datasets]
        self.epsilons = [r.pilot_epsilon(seed_key(PILOT_KEY), cell.quantile,
                                         cell.n_pilot, cell.wave_size)
                         for r in self.refs]

    def fit(self, index: int, d: int, key) -> Fit:
        from bench import reference

        t0 = time.perf_counter()
        c = self.cell
        theta, dist, waves = reference.control_fit(
            self.refs[d], key, self.epsilons[d], c.config["target_accepted"],
            int(c.config["batch_per_chip"]), c.chips, c.max_check_waves)
        return Fit(index, d, key, t0, time.perf_counter() - t0, waves,
                   waves * c.wave_size, theta, dist, self.epsilons[d])


# ---------------------------------------------------------------- the window

_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
           "/jax/core/compile/backend_compile_duration")


class CompileCounter:
    """`count` is the number of traces and compilations (a load from the
    persistent cache counts as one) inside the `with` block."""

    def __init__(self):
        self.count = 0

    def _listen(self, event, duration, **kwargs):
        if event in _EVENTS:
            self.count += 1

    def __enter__(self):
        monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        monitoring.unregister_event_duration_listener(self._listen)
        return False


@dataclasses.dataclass
class Window:
    fits: list
    seconds: float
    setup_s: float
    wave_size: int
    compiles: int


def run_window(fits_of, cell: Cell, seed: int, seconds: float, setup_s: float,
               end_trace: Optional[Callable] = None,
               traced_seconds: float = math.inf) -> Window:
    """Closed loop, one client: fits back to back until `seconds` have
    passed; the window closes when the last fit returns.

    With `end_trace` (a profiler is running) the fits of the window's first
    `traced_seconds` are written as host spans, `bench.fit` inside
    `bench.window`; at the first fit boundary past that, or at the close,
    the spans end and `end_trace()` stops the profiler."""
    import jax

    key = seed_key(seed)
    jax.block_until_ready(jax.random.fold_in(key, 0))
    n = len(cell.config["datasets"])
    done = []
    try:
        with CompileCounter() as counter, contextlib.ExitStack() as traced:
            if end_trace is not None:
                traced.enter_context(
                    jax.profiler.TraceAnnotation("bench.window"))
            t_start = time.perf_counter()
            while (not done or done[-1].start + done[-1].latency - t_start
                   < seconds):
                i = len(done)
                k = jax.random.fold_in(key, i)
                with (jax.profiler.TraceAnnotation("bench.fit", fit=i)
                      if end_trace is not None else contextlib.nullcontext()):
                    done.append(fits_of.fit(i, i % n, k))
                if (end_trace is not None
                        and time.perf_counter() - t_start >= traced_seconds):
                    traced.close()
                    end_trace()
                    end_trace = None
            traced.close()
    finally:
        if end_trace is not None:
            end_trace()
    length = done[-1].start + done[-1].latency - t_start
    return Window(done, length, setup_s, cell.wave_size, counter.count)


# ----------------------------------------------------------------- the check

def sample_fits(fits: list, k: int, seed: int) -> list:
    """The longest fit and k - 1 more drawn from the seed."""
    if len(fits) <= k:
        return list(fits)
    longest = max(range(len(fits)), key=lambda i: (fits[i].waves, -i))
    rest = [i for i in range(len(fits)) if i != longest]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xC0FFEE])
    pick = rng.choice(len(rest), size=k - 1, replace=False)
    return [fits[longest]] + [fits[rest[j]] for j in sorted(pick)]


def reference_epsilons(cell: Cell, datasets, devices=None) -> list[float]:
    """eps of the tolerance pilot, recomputed by the f32 reference."""
    import jax.numpy as jnp

    from bench import reference

    return [reference.Reference(cell.config, ds, jnp.float32,
                                devices).pilot_epsilon(
        seed_key(PILOT_KEY), cell.quantile, cell.n_pilot, cell.wave_size)
        for ds in datasets]


def check(cell: Cell, datasets, epsilons, fits: list, seed: int,
          ref_epsilons: Optional[list] = None, devices=None) -> dict:
    """Every number of `compare` for this run's fits; the reference splits
    its batches over `devices`."""
    import jax.numpy as jnp

    from bench import compare, reference

    c = cell.config
    numbers = compare.window_counts(fits, c["target_accepted"],
                                    cell.wave_size)
    if ref_epsilons is None:
        ref_epsilons = reference_epsilons(cell, datasets, devices)
    numbers["eps_gap"] = max(abs(e - r) / r
                             for e, r in zip(epsilons, ref_epsilons))
    refs = [reference.Reference(c, ds, jnp.float32, devices)
            for ds in datasets]
    band = float(cell.limits.get("dist_gap", 0.0))
    per_fit = [
        compare.check_fit(refs[f.dataset], f, int(c["batch_per_chip"]),
                          cell.chips, c["target_accepted"], band,
                          cell.max_check_waves)
        for f in sample_fits(fits, int(cell.traffic["check_fits"]), seed)
    ]
    numbers.update(compare.worst(per_fit))
    numbers["set_diff"] = float(sum(r["set_diff"] for r in per_fit))
    return numbers
