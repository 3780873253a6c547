"""Trace reduction and the per-layer readers, on traces kept as data: a
hand-built one whose numbers are known, and a small one recorded on a
TPU v5e. Nothing here starts the chip path."""

import gzip
import json
import math
from pathlib import Path

import numpy as np
import pytest

from bench import harness, tracing

TESTDATA = Path(__file__).resolve().parent / "testdata"


def _ev(name, start, dur, **stats):
    return [name, float(start), float(dur), stats]


def _plane(name, modules, ops):
    names = sorted({o[0] for o in ops})
    return {"name": name,
            "modules": [[m, float(s), float(d)] for m, s, d in modules],
            "ops": {"names": names,
                    "id": np.asarray([names.index(o[0]) for o in ops]),
                    "start": np.asarray([o[1] for o in ops], float),
                    "dur": np.asarray([o[2] for o in ops], float)}}


def _hand_trace():
    """Two chips, two fits of 3 and 2 waves, times in ns.

    window [0, 1000]; fit 0 [100, 500], fit 1 [600, 950]
    chip 0 ops: [150, 300] loop, [300, 320] all-reduce inside it,
                [650, 800] loop, [810, 830] copy
    chip 1 ops: [150, 350] loop, [700, 900] loop
    """
    host = {"name": "/host:CPU", "events": [
        _ev("bench.window", 0, 1000),
        _ev("bench.fit", 100, 400, fit=0),
        _ev("PjitFunction(loop)", 120, 40),
        _ev("bench.fit", 600, 350, fit=1),
    ]}
    chip0 = _plane(
        "/device:TPU:0",
        [("jit_loop(1)", 150, 170), ("jit_loop(1)", 650, 150),
         ("jit_copy(2)", 810, 20)],
        [("%fusion.1 = f32[8] fusion(%a)", 150, 150),
         ("%psum.3 = s32[] all-reduce(%b)", 300, 20),
         ("%fusion.1 = f32[8] fusion(%a)", 650, 150),
         ("%copy.4 = f32[8] copy(%c)", 810, 20)])
    chip1 = _plane(
        "/device:TPU:1",
        [("jit_loop(1)", 150, 200), ("jit_loop(1)", 700, 200)],
        [("%fusion.1 = f32[8] fusion(%a)", 150, 200),
         ("%fusion.1 = f32[8] fusion(%a)", 700, 200)])
    return {"planes": [chip0, chip1, host]}


class _Fit:
    def __init__(self, index, waves):
        self.index, self.waves = index, waves


class _Window:
    def __init__(self, fits):
        self.fits = fits


def _ctx(trace, chips, waves, module="jit_loop"):
    from bench.run import TraceContext

    fits = [_Fit(i, w) for i, w in enumerate(waves)]
    return TraceContext(trace, [None] * chips, _Window(fits), module)


def test_union_gaps_and_cover():
    merged = tracing.union([5, 0, 1, 7], [7, 2, 3, 8])
    assert merged.tolist() == [[0, 3], [5, 8]]
    assert tracing.gaps(merged, 1, 10).tolist() == [[3, 5], [8, 10]]
    assert tracing.covered(merged, 2, 6) == 2


def test_hand_trace_readers():
    ctx = _ctx(_hand_trace(), 2, [3, 2])
    assert (ctx.t0, ctx.t1) == (0.0, 1000.0)
    assert ctx.traced_waves() == 5
    # chip 0 busy 340 ns, chip 1 busy 400 ns: the idler is chip 0
    idle = harness.reader("layers", "device_idle_share")(ctx)
    assert math.isclose(idle, 1 - 340 / 1000)
    # loop time: chip 0 320 ns, chip 1 400 ns; slowest over 5 waves
    wave = harness.reader("layers", "wave_device_ms")(ctx)
    assert math.isclose(wave, 400 / 5 / 1e6)
    # fit 0: 400 - max(170, 200); fit 1: 350 - max(170, 200)
    host = harness.reader("layers", "host_ms_per_fit")(ctx)
    assert math.isclose(host, ((400 - 200) + (350 - 200)) / 2 / 1e6)
    # chip 0's all-reduce inside the loop, 20 ns over 5 waves
    coll = harness.reader("layers", "collective_ms_per_wave")(ctx)
    assert math.isclose(coll, 20 / 5 / 1e6)


def test_nothing_to_read_gives_nothing():
    trace = _hand_trace()
    for plane in trace["planes"][:2]:
        plane["ops"]["names"] = [n.replace("psum", "add").replace(
            "all-reduce", "add") for n in plane["ops"]["names"]]
    assert harness.reader("layers", "collective_ms_per_wave")(
        _ctx(trace, 2, [3, 2])) is None
    assert harness.reader("layers", "wave_device_ms")(
        _ctx(trace, 2, [3, 2], module="jit_other")) is None
    bare = {"planes": [trace["planes"][2]]}
    ctx = _ctx(bare, 1, [3, 2])
    assert harness.reader("layers", "device_idle_share")(ctx) is None
    assert harness.reader("layers", "host_ms_per_fit")(ctx) is None


def test_breakdown_names_idle_gaps_by_host_activity():
    trace = _hand_trace()
    out = tracing.breakdown(trace, 0, 1000)
    ops = dict(out["device_ops"])
    assert math.isclose(ops["jit_loop/fusion.1"],
                        (150 + 150 + 200 + 200) / 2 / 1e9)
    assert math.isclose(ops["jit_loop/psum.3"], 20 / 2 / 1e9)
    idle = dict(out["idle_gaps"])
    # chip 0 idles [0, 150): 100 ns between fits, 20 in fit 0's own code,
    # 30 under its dispatch PjitFunction(loop) [120, 160)
    chip0_first = tracing.attribute([(0, 150)], *tracing.host_timeline(
        tracing.host_events(trace)))
    assert chip0_first == {"bench.window": 100, "bench.fit": 20,
                           "bench.fit/PjitFunction(loop)": 30}
    assert "bench.window" in idle and "bench.fit" in idle
    assert math.isclose(sum(idle.values()),
                        ((1000 - 340) + (1000 - 400)) / 2 / 1e9)
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


def _recorded():
    """The first four fits of a traced window of one-wave SIARD fits on a
    TPU v5e (`bench/run.py --trace 1`, seed 2147483950, at a tolerance
    loose enough for one wave a fit): the window span is cut to end with
    the fourth fit, and every event after it dropped."""
    with gzip.open(TESTDATA / "trace_siard3_shallow.json.gz", "rt") as f:
        return tracing.from_json(json.load(f))


def test_recorded_trace_reduces():
    trace = _recorded()
    planes = tracing.device_planes(trace)
    assert len(planes) == 1
    t0, t1 = tracing.window(trace)
    fits = tracing.spans(trace, "bench.fit")
    assert len(fits) == 4 and all(t0 <= s and e <= t1 for s, e, _ in fits)
    busy = tracing.busy(planes[0])
    covered = tracing.covered(busy, t0, t1)
    idle = tracing.gaps(busy, t0, t1)
    assert math.isclose(covered + float(np.sum(idle[:, 1] - idle[:, 0])),
                        t1 - t0, rel_tol=1e-9)
    # every fit runs the wave loop once, on the chip, inside its span
    loops = tracing.module_intervals(planes[0], "jit_loop")
    assert len(loops) == len(fits)
    for (s, e, _), (ls, le) in zip(fits, loops):
        assert s <= ls and le <= e
    out = tracing.breakdown(trace, t0, t1)
    top = out["device_ops"][0][0]
    assert top.startswith("jit_loop/")
    assert len(out["idle_gaps"]) <= 10
    labels = tracing.attribute(idle, *tracing.host_timeline(
        tracing.host_events(trace)))
    assert math.isclose(sum(labels.values()), t1 - t0 - covered,
                        rel_tol=1e-9)
    assert {"bench.fit", "bench.window"} <= set(labels)


@pytest.mark.parametrize("name", ["device_idle_share", "wave_device_ms",
                                  "host_ms_per_fit"])
def test_recorded_trace_readers_give_numbers(name):
    trace = _recorded()
    ctx = _ctx(trace, 1, [1, 1, 1, 1])
    value = harness.reader("layers", name)(ctx)
    assert value is not None and value > 0 and math.isfinite(value)
    if name == "device_idle_share":
        assert value < 1


def test_one_chip_trace_has_no_exchange_to_read():
    ctx = _ctx(_recorded(), 1, [1, 1, 1, 1])
    assert harness.reader("layers", "collective_ms_per_wave")(ctx) is None
