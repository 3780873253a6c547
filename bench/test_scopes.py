"""The readers of the program's named scopes and host spans (`scopes.py`
and the five per-layer metrics built on it), on traces kept as data: a
hand-built one whose numbers are known, the recorded ones, and a wave loop
compiled on the CPU for the scope column's look-up."""

import gzip
import json
import math

import jax
import numpy as np
import pytest

from bench import harness, scopes, tracing
from bench.test_tracing import TESTDATA, _ctx, _ev, _plane, _recorded

READERS = ("sim_ns_per_sample_day", "prior_ms_per_wave",
           "accept_ms_per_wave", "init_ms_per_fit", "harvest_ms_per_fit")
BODY = "jit(loop)/while/body/"
#: batch per chip and days of the hand-built trace's fits
B, DAYS = 4, 10


def _scoped(plane, scope_of):
    plane["ops"]["scopes"] = [scope_of.get(tracing.short_name(n), "")
                              for n in plane["ops"]["names"]]
    return plane


def _hand_trace():
    """Two chips, two fits of 3 and 2 waves, times in ns.

    window [0, 1000]; fit 0 [100, 500], fit 1 [600, 950]
    host: init [110, 130] and [610, 620], harvest [420, 460] and
          [900, 940]; one more harvest [960, 980] outside the fits
    chip 0 loop ops: prior 20 + 10, simulate 180 + 200, accept 30 + 20,
          an unscoped add, and a simulate op of another executable
    chip 1 loop ops: prior 10 + 5, simulate 220 + 215, accept 10 + 15
    """
    def harvest(start, waves):
        return _ev("abc.harvest", start, 40, sample_days=waves * B * DAYS)

    host = {"name": "/host:CPU", "events": [
        _ev("bench.window", 0, 1000),
        _ev("bench.fit", 100, 400, fit=0),
        _ev("abc.init", 110, 20),
        _ev("abc.wave_loop", 130, 290),
        harvest(420, 3),
        _ev("bench.fit", 600, 350, fit=1),
        _ev("abc.init", 610, 10),
        _ev("abc.wave_loop", 620, 280),
        harvest(900, 2),
        harvest(960, 9),
    ]}
    scope_of = {"fusion.1": BODY + "abc.prior/jit(_uniform)/add",
                "fusion.2": BODY + "abc.simulate/while/body/floor",
                "fusion.3": BODY + "abc.accept/scatter",
                "add.4": BODY + "add"}
    prior, sim, accept = ("%fusion.1 = f32[8] fusion(%k)",
                          "%fusion.2 = f32[8] fusion(%t)",
                          "%fusion.3 = f32[8] fusion(%d)")
    chip0 = _plane(
        "/device:TPU:0",
        [("jit_loop(1)", 150, 250), ("jit_loop(1)", 650, 240),
         ("jit_other(2)", 960, 10)],
        [(prior, 150, 20), (sim, 170, 180), (accept, 350, 30),
         ("%add.4 = s32[] add(%n)", 380, 10),
         (prior, 650, 10), (sim, 660, 200), (accept, 860, 20),
         (sim, 960, 10)])
    chip1 = _plane(
        "/device:TPU:1",
        [("jit_loop(1)", 150, 240), ("jit_loop(1)", 650, 235)],
        [(prior, 150, 10), (sim, 160, 220), (accept, 380, 10),
         (prior, 650, 5), (sim, 655, 215), (accept, 870, 15)])
    return {"planes": [_scoped(chip0, scope_of), _scoped(chip1, scope_of),
                       host]}


def _read(name, ctx):
    return harness.reader("layers", name)(ctx)


def test_hand_trace_scope_and_span_readers():
    ctx = _ctx(_hand_trace(), 2, [3, 2])
    # slowest chip's simulate time, 435 ns, over 3 x 4 x 10 + 2 x 4 x 10
    assert math.isclose(_read("sim_ns_per_sample_day", ctx), 435 / 200)
    # chip 0 spends most in the prior (30 ns) and acceptance (50 ns)
    assert math.isclose(_read("prior_ms_per_wave", ctx), 30 / 5 / 1e6)
    assert math.isclose(_read("accept_ms_per_wave", ctx), 50 / 5 / 1e6)
    # the spans inside the two traced fits, per fit
    assert math.isclose(_read("init_ms_per_fit", ctx), 30 / 2 / 1e6)
    assert math.isclose(_read("harvest_ms_per_fit", ctx), 80 / 2 / 1e6)


def test_scope_intervals_keep_to_the_executable():
    plane = _hand_trace()["planes"][0]
    sim = scopes.scope_intervals(plane, "abc.simulate", "jit_loop")
    assert sim.tolist() == [[170, 350], [660, 860]]
    assert scopes.scope_intervals(plane, "abc.simulate",
                                  "jit_other").tolist() == [[960, 970]]
    # a scope is a whole component of the op_name, not a part of one
    assert len(scopes.scope_intervals(plane, "abc.sim", "jit_loop")) == 0


def test_nothing_to_read_gives_nothing():
    bare = _hand_trace()
    for plane in bare["planes"][:2]:
        del plane["ops"]["scopes"]
    ctx = _ctx(bare, 2, [3, 2])
    # no scope column and no program to look the scopes up in
    for name in ("sim_ns_per_sample_day", "prior_ms_per_wave",
                 "accept_ms_per_wave"):
        assert _read(name, ctx) is None
    unspanned = _hand_trace()
    host = unspanned["planes"][2]
    host["events"] = [e for e in host["events"]
                      if not e[0].startswith("abc.")]
    ctx = _ctx(unspanned, 2, [3, 2])
    for name in ("sim_ns_per_sample_day", "init_ms_per_fit",
                 "harvest_ms_per_fit"):
        assert _read(name, ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_trace_of_a_program_without_them_gives_nothing(name):
    """The committed trace of the program before its scopes and spans."""
    assert _read(name, _ctx(_recorded(), 1, [1, 1, 1, 1])) is None


def test_op_scopes_reads_optimized_hlo():
    text = "\n".join([
        "ENTRY %main {",
        '  %add.1 = f32[] add(%a, %b), metadata={op_type="add" '
        'op_name="jit(loop)/while/body/abc.prior/add" source_line=3}',
        "  %copy.2 = f32[] copy(%add.1)",
        '  ROOT %fusion.3 = f32[] fusion(%copy.2), kind=kLoop, '
        'calls=%f, metadata={op_name="jit(loop)/abc.accept/mul"}',
        "}"])
    assert scopes.op_scopes(text) == {
        "add.1": "jit(loop)/while/body/abc.prior/add",
        "fusion.3": "jit(loop)/abc.accept/mul"}


def _program(runners):
    """A stand-in for the harness's program, holding only its runners."""
    program = object.__new__(harness.ProgramFits)
    program.runners = runners
    return program


class _Fit:
    def __init__(self, index, waves, dataset):
        self.index, self.waves, self.dataset = index, waves, dataset


def _fits_ctx(trace, chips, fits):
    """A trace context whose fits (index, waves, dataset) are given."""
    from bench.run import TraceContext

    window = type("Window", (), {"fits": [_Fit(*f) for f in fits]})()
    return TraceContext(trace, [None] * chips, window, "jit_loop")


class _Lowered:
    """A stand-in for a lowered wave loop: the text of the executable that
    JAX's caches hold for it, and of a fresh compile, which it counts."""

    def __init__(self, ran, fresh=None, scoped=True):
        self.ran, self.fresh, self.scoped = ran, fresh, scoped
        self.fresh_compiles = 0

    def as_text(self, debug_info=False):
        return 'loc("jit(loop)/while/body/abc.prior/add")' if (
            debug_info and self.scoped) else ""

    def compile(self, options=None):
        if options is None:
            text = self.ran
        else:
            self.fresh_compiles += 1
            text = self.fresh
        return type("Compiled", (), {"as_text": lambda _: text})()


def _hlo(*lines):
    return "\n".join(f'  %{name} = {body}, metadata={{op_name="{op}"}}'
                     for name, body, op in lines)


def test_each_op_takes_the_scopes_of_the_executable_that_ran_it(
        monkeypatch):
    """Two datasets' loops number their instructions apart: x.1 is the
    prior in one and the simulator in the other."""
    loops = {"a": _Lowered(_hlo(("x.1", "f32[] add()", "loop/abc.prior/a"),
                                ("x.2", "f32[] or()", "loop/abc.accept/o"))),
             "b": _Lowered(_hlo(("x.1", "f32[] mul()", "loop/abc.simulate/m"),
                                ("x.2", "f32[] or()", "loop/abc.accept/o")))}
    monkeypatch.setattr(scopes, "loop_lowering", loops.get)
    trace = _hand_trace()
    plane = trace["planes"][1]
    names = ["%x.1 = f32[] add()", "%x.1 = f32[] mul()", "%x.2 = f32[] or()",
             "%x.3 = f32[] sub()"]
    plane["modules"] = [["jit_loop(1)", 150.0, 100.0],
                        ["jit_loop(2)", 650.0, 100.0]]
    plane["ops"] = {"names": names, "id": np.asarray([0, 2, 1, 2, 3]),
                    "start": np.asarray([150.0, 200.0, 650.0, 700.0, 960.0]),
                    "dur": np.full(5, 10.0)}
    program = _program(["a", "b"])  # found on this frame by `attach`
    scopes.attach(_fits_ctx(trace, 2, [(0, 3, 0), (1, 2, 1)]))
    assert plane["ops"]["scopes"] == ["loop/abc.prior/a", "loop/abc.simulate/m",
                                      "loop/abc.accept/o", ""]
    assert program.runners == ["a", "b"]


def test_op_names_of_another_program_are_looked_up_afresh(monkeypatch):
    """The executables came from a cache entry of a program without the
    scopes: a loop is compiled afresh, once for the loops that hold the
    same instructions, and its op_names are kept where the fresh compile
    holds the instructions that ran."""
    ran = _hlo(("x.1", "f32[] add()", "loop/add"),
               ("x.2", "f32[] or()", "loop/or"))
    fresh = _hlo(("x.1", "f32[] add()", "loop/abc.prior/add"),
                 ("x.2", "f32[] or()", "loop/abc.accept/or"))
    other = _hlo(("y.1", "f32[] add()", "loop/add"))
    loops = {"a": _Lowered(ran, fresh), "b": _Lowered(ran, fresh),
             "c": _Lowered(other, fresh),
             "d": _Lowered(fresh),
             "e": _Lowered(other, scoped=False)}
    monkeypatch.setattr(scopes, "loop_lowering", loops.get)
    maps = scopes.loop_scopes(list("abcde"))
    assert maps[0] == maps[1] == maps[3] == {"x.1": "loop/abc.prior/add",
                                             "x.2": "loop/abc.accept/or"}
    assert sum(loops[k].fresh_compiles for k in "ab") == 1
    # a fresh compile that does not hold what ran gives nothing; a program
    # that names no steps is not compiled again
    assert maps[2] == {} and maps[4] == {"y.1": "loop/add"}
    assert loops["d"].fresh_compiles == loops["e"].fresh_compiles == 0


def test_attach_looks_scopes_up_in_the_program_wave_loops():
    """On the CPU: the column comes from the compiled loop of a wave runner
    that the harness's program holds on the caller's stack."""
    from repro.core.abc import (ABCConfig, ABCState, make_simulator,
                                make_wave_runner)
    from repro.epi.data import get_dataset
    from repro.epi.models import get_model

    cfg = ABCConfig(batch_size=256, chunk_size=256, num_days=10,
                    tolerance=1e6, target_accepted=10, max_runs=3)
    runner = make_wave_runner(
        get_model("siard").prior(),
        make_simulator(get_dataset("synthetic_small", num_days=10), cfg), cfg)
    runner(jax.random.PRNGKey(0), 0,
           runner.init(ABCState(n_params=runner.n_params)), 1)
    found, = scopes.loop_scopes([runner])
    picks = {scope: next(n for n, s in found.items()
                         if scope in s.split("/"))
             for scope in ("abc.prior", "abc.simulate", "abc.accept")}
    trace = _hand_trace()
    plane = trace["planes"][0]
    plane["ops"] = {"names": [f"%{n} = f32[] op()" for n in picks.values()]
                    + ["%unknown.9 = f32[] op()"],
                    "id": np.arange(4), "start": np.asarray([150.0] * 4),
                    "dur": np.ones(4)}
    trace["planes"] = [plane, trace["planes"][2]]
    program = _program([runner])  # noqa: F841 — found on the stack
    scopes.attach(_fits_ctx(trace, 1, [(0, 3, 0), (1, 2, 0)]))
    got = plane["ops"]["scopes"]
    assert [scope in s.split("/") for scope, s in zip(picks, got)] == \
        [True] * 3
    assert got[3] == ""


def _scoped_recording():
    """The first four fits of a traced `siard3.deep` window on a TPU v5e
    (`bench/run.py --trace 1`, seed 2147484011) with the scope column that
    `scopes.attach` gave it there: the window span is cut to end with the
    fourth fit, and every event after it dropped."""
    with gzip.open(TESTDATA / "trace_siard3_scoped.json.gz", "rt") as f:
        trace = tracing.from_json(json.load(f))
    waves = [int(s[2]["sample_days"]) // (100_000 * 49)
             for s in tracing.spans(trace, "abc.harvest")]
    return _ctx(trace, 1, waves)


@pytest.mark.parametrize("name", READERS)
def test_scoped_recording_readers_give_numbers(name):
    value = _read(name, _scoped_recording())
    assert value is not None and value > 0 and math.isfinite(value)


def test_scoped_recording_scopes_hold_most_of_a_wave():
    ctx = _scoped_recording()
    per_wave = (_read("prior_ms_per_wave", ctx)
                + _read("accept_ms_per_wave", ctx)
                + _read("sim_ns_per_sample_day", ctx) * 100_000 * 49 / 1e6)
    assert per_wave >= 0.9 * _read("wave_device_ms", ctx)
