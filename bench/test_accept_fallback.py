"""The reader of `accept_fallback_share` on traces kept as data: the
hand-built trace of `test_scopes.py`, whose waves never fall back, the
same trace with fallback operations added, and the recorded one."""

import math

import pytest

from bench import harness, scopes
from bench.test_scopes import (BODY, _ctx, _ev, _hand_trace, _plane,
                               _program, _scoped, _scoped_recording)

FALLBACK = BODY + "abc.accept/cond/branch_0_fun/abc.accept_fallback/"


class _Lowering:
    def __init__(self, text):
        self.text = text

    def as_text(self, debug_info=False):
        return self.text if debug_info else ""


def _read(ctx):
    return harness.reader("layers", "accept_fallback_share")(ctx)


@pytest.fixture
def branch(monkeypatch):
    """The program's wave loop holds the fallback branch."""
    monkeypatch.setattr(scopes, "loop_lowering", lambda runner: _Lowering(
        f'loc("{FALLBACK}scatter")'))


def _fallback_trace():
    """One chip, fits of 3 and 2 waves, times in ns.

    fit 0 [100, 500]: waves simulate, accept and fall back at [210, 215]
          and [217, 220], split by an unscoped copy; simulate and accept;
          simulate and fall back at [345, 350]
    fit 1 [600, 950]: simulate and accept; simulate and fall back at
          [765, 770]; one more fallback op of another executable at 960
    """
    host = {"name": "/host:CPU", "events": [
        _ev("bench.window", 0, 1000),
        _ev("bench.fit", 100, 400, fit=0),
        _ev("bench.fit", 600, 350, fit=1),
    ]}
    sim, accept = "%fusion.2 = f32[8] fusion(%t)", "%fusion.3 = f32[8] fusion(%d)"
    scatter, copy = "%fusion.5 = f32[8] fusion(%s)", "%copy.6 = f32[8] copy(%s)"
    chip = _plane(
        "/device:TPU:0",
        [("jit_loop(1)", 150, 250), ("jit_loop(1)", 650, 240),
         ("jit_other(2)", 960, 10)],
        [(sim, 160, 40), (accept, 200, 10), (scatter, 210, 5), (copy, 215, 2),
         (scatter, 217, 3), (sim, 230, 40), (accept, 270, 10),
         (sim, 300, 40), (accept, 340, 5), (scatter, 345, 5),
         (sim, 660, 40), (accept, 700, 10),
         (sim, 720, 40), (accept, 760, 5), (scatter, 765, 5),
         (scatter, 960, 10)])
    scope_of = {"fusion.2": BODY + "abc.simulate/floor",
                "fusion.3": BODY + "abc.accept/lt",
                "fusion.5": FALLBACK + "scatter"}
    return {"planes": [_scoped(chip, scope_of), host]}


def test_share_of_waves_that_fell_back(branch):
    program = _program(["loop"])  # noqa: F841 — found on the stack
    # 3 of the 5 waves fell back; the split run is one wave, and the op of
    # another executable is not one
    assert math.isclose(_read(_ctx(_fallback_trace(), 1, [3, 2])), 3 / 5)


@pytest.mark.parametrize("recorded", [False, True], ids=["hand", "recorded"])
def test_waves_without_a_fallback_read_zero(branch, recorded):
    program = _program(["loop"])  # noqa: F841 — found on the stack
    ctx = _scoped_recording() if recorded else _ctx(_hand_trace(), 2, [3, 2])
    assert _read(ctx) == 0.0


def test_a_program_without_the_branch_gives_nothing(monkeypatch):
    monkeypatch.setattr(scopes, "loop_lowering", lambda runner: _Lowering(
        f'loc("{BODY}abc.accept/scatter")'))
    program = _program(["loop"])  # noqa: F841 — found on the stack
    assert _read(_ctx(_fallback_trace(), 1, [3, 2])) is None
    del program
    # nor does a trace read with no program to look the loop up in
    assert _read(_ctx(_fallback_trace(), 1, [3, 2])) is None


def test_no_traced_waves_give_nothing(branch):
    program = _program(["loop"])  # noqa: F841 — found on the stack
    assert _read(_ctx(_fallback_trace(), 1, [])) is None
