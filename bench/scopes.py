"""Device time under the program's named scopes, and the program's host
spans.

The wave loop runs its three steps under `jax.named_scope`s (`abc.prior`,
`abc.simulate`, `abc.accept`), which XLA keeps in each operation's
`op_name` metadata; the fit driver opens host spans `abc.init`,
`abc.wave_loop`, `abc.harvest` and `abc.posterior`, of which
`abc.harvest` carries a counter (`abc.harvest(sample_days=)`).

`tracing.load_xplane` keeps an operation's HLO text, which leaves the
metadata out. So, after the window, the scope of each operation is looked
up in the optimized HLO text of the wave-loop executable that ran it (one
per dataset, each numbering its instructions its own way) and kept as one
more interned column of the reduced trace: `ops["scopes"]`, the `op_name`
of each entry of `ops["names"]` ("" where none is known). A trace that
already carries the column, such as a recorded one, is read as it is; a
program without the scopes leaves every reader here with nothing to read.
"""

from __future__ import annotations

import concurrent.futures
import re
import sys

import numpy as np

from bench import tracing

#: an instruction of optimized HLO text and the `op_name` of its metadata
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%(\S+) = .*?metadata=\{[^}]*?op_name="([^"]*)"')
_DEFINITION = re.compile(r"^\s*(?:ROOT )?%(\S+) = ", re.MULTILINE)
#: a step of the wave loop in an op_name or a lowering's locations
_SCOPED = re.compile(r"/abc\.(prior|simulate|accept)/")
#: a compile option at its default value: with it the loop compiles afresh,
#: past JAX's in-memory and persistent caches, into the same program
_FRESH = {"xla_embed_ir_in_executable": False}


def op_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> `op_name`, from optimized HLO text."""
    return {m.group(1): m.group(2) for line in hlo_text.splitlines()
            if (m := _INSTRUCTION.match(line))}


def loop_lowering(runner):
    """A wave runner's loop, lowered from the arguments that the runner's
    own call passes, so that it compiles to the executable the window ran."""
    from repro.core.abc import ABCState

    import jax

    lowered = []
    fn = runner.fn
    runner.fn = lambda *args: lowered.append(fn.lower(*args))
    try:
        runner(jax.random.PRNGKey(0), 0,
               runner.init(ABCState(n_params=runner.n_params)), 1)
    finally:
        runner.fn = fn
    return lowered[0]


def loop_scopes(runners) -> list[dict[str, str]]:
    """Instruction name -> `op_name` of each runner's wave loop, as the
    window ran it.

    JAX finds those executables in its caches. The persistent cache's key
    leaves metadata out, so an executable may come from another program of
    the same operations, with that program's op_names. A loop whose
    executable names no step, though its lowering does, is compiled afresh
    after the window (several seconds; the compiles run side by side, and
    loops whose executables hold the same instructions share one) and its
    op_names are kept if it holds the same instructions as what ran."""
    lowered = [loop_lowering(r) for r in runners]
    ran = [low.compile().as_text() for low in lowered]
    # the instructions of each executable that names no step, though its
    # lowering does; None where the executable's own op_names serve
    stale = [tuple(_DEFINITION.findall(text))
             if not _SCOPED.search(text)
             and _SCOPED.search(low.as_text(debug_info=True)) else None
             for low, text in zip(lowered, ran)]
    once: dict = {}
    for names, low in zip(stale, lowered):
        if names:
            once.setdefault(names, low)
    with concurrent.futures.ThreadPoolExecutor(max(1, len(once))) as pool:
        fresh = {names: pool.submit(lambda l: l.compile(_FRESH).as_text(),
                                    low) for names, low in once.items()}
        fresh = {names: text.result() for names, text in fresh.items()}
    return [op_scopes(text) if names is None
            else op_scopes(fresh[names])
            if tuple(_DEFINITION.findall(fresh[names])) == names else {}
            for names, text in zip(stale, ran)]


def _program_runners() -> list:
    """The wave runners of the program under test. `run.run` holds its
    `harness.ProgramFits` on the stack while the per-layer readers run; the
    trace context is not handed it."""
    from bench import harness

    frame = sys._getframe(1)
    while frame is not None:
        for value in frame.f_locals.values():
            if isinstance(value, harness.ProgramFits):
                return list(value.runners)
        frame = frame.f_back
    return []


def _module_runners(ctx, plane) -> dict[str, int]:
    """Executable (by its name and id) -> index of the runner that ran it:
    fit i runs the runner of its dataset, one per dataset."""
    found: dict[str, set] = {}
    for a, b, stats in ctx.fit_spans():
        fit = ctx.fits.get(int(stats["fit"]))
        for name, start, dur in plane["modules"]:
            if fit is not None and a <= start and start + dur <= b:
                found.setdefault(name, set()).add(fit.dataset)
    return {name: d.pop() for name, d in found.items() if len(d) == 1}


def _column(plane, maps, module_runner) -> list[str]:
    """The op_name of each of the plane's operation names, from the map of
    the runner whose executable ran it ("" where runners disagree)."""
    ops = plane["ops"]
    mods = sorted(plane["modules"], key=lambda m: m[1])
    found = [set() for _ in ops["names"]]
    if mods and ops["id"].size:
        start = np.asarray([m[1] for m in mods], np.float64)
        end = start + np.asarray([m[2] for m in mods], np.float64)
        runner = np.asarray([module_runner.get(m[0], -1) for m in mods])
        i = np.searchsorted(start, ops["start"], side="right") - 1
        inside = (i >= 0) & (ops["start"] < end[np.maximum(i, 0)])
        r = np.where(inside, runner[np.maximum(i, 0)], -1)
        width = len(maps) + 1
        for pair in np.unique(ops["id"][r >= 0] * width + r[r >= 0]):
            k, j = divmod(int(pair), width)
            found[k].add(maps[j].get(tracing.short_name(ops["names"][k]), ""))
    return [f.pop() if len(f) == 1 else "" for f in found]


def attach(ctx) -> None:
    """Give every chip plane of `ctx` its `scopes` column, from the wave
    loops of the program under test where the trace lacks it."""
    missing = [p for p in ctx.planes if "scopes" not in p["ops"]]
    if not missing:
        return
    maps = loop_scopes(_program_runners())
    for plane in missing:
        plane["ops"]["scopes"] = (
            _column(plane, maps, _module_runners(ctx, plane)) if maps
            else [""] * len(plane["ops"]["names"]))


def scope_intervals(plane: dict, scope: str, module: str) -> np.ndarray:
    """Merged runs of the operations under the named scope `scope` (a
    component of their `op_name`), inside runs of the executable `module`."""
    ops = plane["ops"]
    under = [scope in s.split("/") for s in ops.get("scopes", [])]
    if len(under) != len(ops["names"]) or not any(under):
        return np.zeros((0, 2))
    take = np.asarray(under + [False])[ops["id"]]
    take &= tracing._module_of(plane, ops["start"]) == module
    return tracing.union(ops["start"][take],
                         ops["start"][take] + ops["dur"][take])


def scope_ns(ctx, scope: str) -> float:
    """Device ns under `scope` in the wave-loop executable within the
    window, on the chip that spends most there."""
    attach(ctx)
    return max((tracing.covered(scope_intervals(p, scope, ctx.wave_module),
                                ctx.t0, ctx.t1) for p in ctx.planes),
               default=0.0)


def fit_spans(ctx, name: str) -> list[tuple[float, float, dict]]:
    """The host spans `name` that lie inside the traced fits."""
    fits = ctx.fit_spans()
    return [s for s in tracing.spans(ctx.trace, name)
            if any(a <= s[0] and s[1] <= b for a, b, _ in fits)]


def ms_per_fit(ctx, name: str):
    """Host ms of the spans `name` per traced fit, or None without them."""
    found = fit_spans(ctx, name)
    if not found:
        return None
    return sum(e - s for s, e, _ in found) / len(ctx.fit_spans()) / 1e6


def ms_per_wave(ctx, scope: str):
    """Device ms under `scope` per traced wave, slowest chip, or None."""
    waves = ctx.traced_waves()
    ns = scope_ns(ctx, scope)
    if not waves or not ns:
        return None
    return ns / waves / 1e6
