"""Reduction of a profiler trace to the numbers the per-layer readers need.

A trace is kept as plain data, so that a small recorded one can be checked
in a test without the chip:

  {"planes": [
     {"name": "/device:TPU:0",
      "modules": [[name, start_ns, dur_ns], ...],          # "XLA Modules"
      "ops": {"names": [hlo text, ...], "id": [...],       # "XLA Ops"
              "start": [...], "dur": [...]}},
     {"name": "/host:CPU", "events": [[name, start_ns, dur_ns, stats], ...]}]}

On a device plane "XLA Modules" holds one event per executable run and
"XLA Ops" one per operation; a 10-second window of the wave loop holds
over a million of those, so they are kept as numpy arrays. The host plane
keeps the Python thread's spans: the benchmark's own (`bench.window`,
`bench.fit`) and JAX's dispatch spans, on the same clock as the device.
Interval sets are [n, 2] arrays of merged, sorted (start, end) rows.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

OUTSIDE = "outside any host span"
#: operations that hold other operations (a loop and its body): their time
#: is their body's, so the table of operations leaves them out
CONTAINER = re.compile(r"\s(while|conditional|call)\(")
#: operations that exchange data between chips
COLLECTIVE = re.compile(
    r"\s(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?\(")


def _stats(event) -> dict:
    return {str(k): v for k, v in dict(event.stats).items()
            if isinstance(v, (int, float, str))}


def _ops(line) -> dict:
    names: dict[str, int] = {}
    ids, start, dur = [], [], []
    for e in line.events:
        n = e.name
        i = names.get(n)
        if i is None:
            i = names[n] = len(names)
        ids.append(i)
        start.append(e.start_ns)
        dur.append(e.duration_ns)
    return {"names": list(names), "id": np.asarray(ids, np.int64),
            "start": np.asarray(start, np.float64),
            "dur": np.asarray(dur, np.float64)}


def _ops_empty() -> dict:
    return {"names": [], "id": np.zeros(0, np.int64),
            "start": np.zeros(0), "dur": np.zeros(0)}


def load_xplane(directory: str) -> dict:
    """The newest `.xplane.pb` under `directory`, as plain data."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            out = {"name": plane.name, "modules": [], "ops": _ops_empty()}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    out["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                      for e in line.events]
                elif line.name == "XLA Ops":
                    out["ops"] = _ops(line)
            planes.append(out)
        elif plane.name.startswith("/host:CPU"):
            events = [[e.name, e.start_ns, e.duration_ns, _stats(e)]
                      for line in plane.lines
                      if line.name.startswith("python")
                      for e in line.events]
            planes.append({"name": plane.name, "events": events})
    return {"planes": planes}


def from_json(trace: dict) -> dict:
    """A trace read back from JSON, with its op columns as arrays."""
    for p in trace["planes"]:
        if "ops" in p:
            ops = p["ops"]
            ops["id"] = np.asarray(ops["id"], np.int64)
            ops["start"] = np.asarray(ops["start"], np.float64)
            ops["dur"] = np.asarray(ops["dur"], np.float64)
    return trace


def device_planes(trace: dict, platform: str = "TPU") -> list[dict]:
    """The planes of the chips, in device order."""
    pat = re.compile(rf"^/device:{platform}:(\d+)$")
    found = [(int(m.group(1)), p) for p in trace["planes"]
             if (m := pat.match(p["name"]))]
    return [p for _, p in sorted(found, key=lambda x: x[0])]


def host_events(trace: dict) -> list:
    return [e for p in trace["planes"] if p["name"].startswith("/host:CPU")
            for e in p["events"]]


def spans(trace: dict, name: str) -> list[tuple[float, float, dict]]:
    """(start_ns, end_ns, stats) of the host spans called `name`."""
    return sorted((e[1], e[1] + e[2], e[3]) for e in host_events(trace)
                  if e[0] == name)


def window(trace: dict) -> tuple[float, float]:
    """The measured window: the `bench.window` span."""
    w = spans(trace, "bench.window")
    if not w:
        raise ValueError("trace holds no bench.window span")
    return w[0][0], w[0][1]


def union(starts, ends) -> np.ndarray:
    """Merged intervals of the given (start, end) pairs."""
    s = np.asarray(starts, np.float64)
    e = np.asarray(ends, np.float64)
    if s.size == 0:
        return np.zeros((0, 2))
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return np.stack([s[first], reach[last]], axis=1)


def clip(intervals, t0: float, t1: float) -> np.ndarray:
    iv = np.clip(np.asarray(intervals, np.float64).reshape(-1, 2), t0, t1)
    return iv[iv[:, 1] > iv[:, 0]]


def covered(intervals, t0: float, t1: float) -> float:
    """Length of [t0, t1] that the merged `intervals` cover."""
    iv = clip(intervals, t0, t1)
    return float(np.sum(iv[:, 1] - iv[:, 0]))


def busy(plane: dict) -> np.ndarray:
    """Merged intervals in which an operation ran on this chip."""
    ops = plane["ops"]
    return union(ops["start"], ops["start"] + ops["dur"])


def gaps(intervals, t0: float, t1: float) -> np.ndarray:
    """The idle intervals of [t0, t1] between the merged busy `intervals`."""
    iv = clip(intervals, t0, t1)
    edges = np.concatenate([[t0], iv.ravel(), [t1]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def module_intervals(plane: dict, prefix: str) -> np.ndarray:
    """Runs of the executable jitted as `prefix` (e.g. `jit_loop`)."""
    runs = [(m[1], m[1] + m[2]) for m in plane["modules"]
            if m[0] == prefix or m[0].startswith(prefix + "(")]
    if not runs:
        return np.zeros((0, 2))
    return union(*zip(*runs))


def op_intervals(plane: dict, pattern: re.Pattern, module: str) -> np.ndarray:
    """Merged runs of the operations whose HLO text matches `pattern`,
    inside runs of the executable `module` (e.g. `jit_loop`)."""
    ops = plane["ops"]
    hit = np.asarray([bool(pattern.search(n)) for n in ops["names"]] + [False])
    take = hit[ops["id"]] if ops["id"].size else np.zeros(0, bool)
    take &= _module_of(plane, ops["start"]) == module
    return union(ops["start"][take], ops["start"][take] + ops["dur"][take])


def short_name(hlo: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _module_of(plane: dict, t: np.ndarray) -> np.ndarray:
    """Name (without its id) of the executable running at each time `t`."""
    mods = sorted(plane["modules"], key=lambda m: m[1])
    if not mods:
        return np.full(t.shape, "?", object)
    start = np.asarray([m[1] for m in mods])
    end = np.asarray([m[1] + m[2] for m in mods])
    names = np.asarray([m[0].split("(", 1)[0] for m in mods] + ["?"], object)
    i = np.searchsorted(start, t, side="right") - 1
    inside = (i >= 0) & (t < end[np.maximum(i, 0)])
    return names[np.where(inside, i, len(mods))]


def _label(stack: list) -> str:
    if not stack:
        return OUTSIDE
    inner = stack[-1][0]
    if inner.startswith("bench."):
        return inner
    in_fit = any(e[0] == "bench.fit" for e in stack)
    return ("bench.fit/" if in_fit else "bench.window/") + inner


def host_timeline(host: list) -> tuple[np.ndarray, list[str]]:
    """The host thread's time cut where its spans begin or end: rows
    (start, end, label index) and the labels, each piece named after the
    innermost span open over it, under the benchmark span that holds it.
    Spans of one thread nest, so a stack follows them."""
    marks = sorted({t for e in host for t in (e[1], e[1] + e[2])})
    starts = sorted(host, key=lambda e: (e[1], -e[2]))
    labels: dict[str, int] = {}
    rows, stack, k = [], [], 0
    for a, b in zip(marks, marks[1:]):
        stack = [e for e in stack if e[1] + e[2] > a]
        while k < len(starts) and starts[k][1] <= a:
            if starts[k][1] + starts[k][2] > a:
                stack.append(starts[k])
            k += 1
        label = labels.setdefault(_label(stack), len(labels))
        rows.append((a, b, label))
    return np.asarray(rows, np.float64).reshape(-1, 3), list(labels)


def attribute(idle, timeline: np.ndarray, labels: list[str]) -> dict:
    """ns of the `idle` intervals spent under each host label."""
    idle = np.asarray(idle, np.float64).reshape(-1, 2)
    if idle.size == 0:
        return {}
    cuts = np.unique(np.concatenate([idle.ravel(), timeline[:, :2].ravel()]))
    mid = 0.5 * (cuts[:-1] + cuts[1:])
    length = np.diff(cuts)
    g = np.searchsorted(idle[:, 0], mid, side="right") - 1
    in_idle = (g >= 0) & (mid < idle[np.maximum(g, 0), 1])
    if timeline.size:
        t = np.searchsorted(timeline[:, 0], mid, side="right") - 1
        in_span = (t >= 0) & (mid < timeline[np.maximum(t, 0), 1])
        label = np.where(in_span, timeline[np.maximum(t, 0), 2], len(labels))
    else:
        label = np.full(mid.shape, len(labels))
    sums = np.bincount(label[in_idle].astype(np.int64),
                       weights=length[in_idle], minlength=len(labels) + 1)
    names = labels + [OUTSIDE]
    out: dict[str, float] = {}
    for i, v in enumerate(sums):
        if v > 0:
            out[names[i]] = out.get(names[i], 0.0) + float(v)
    return out


def breakdown(trace: dict, t0: float, t1: float, top: int = 10) -> dict:
    """Device operations by time and idle time by host activity, in seconds
    averaged over the chips, the largest `top` of each."""
    planes = device_planes(trace)
    timeline, labels = host_timeline(host_events(trace))
    ops_s: dict[str, float] = {}
    idle: dict[str, float] = {}
    for plane in planes:
        ops = plane["ops"]
        s = np.clip(ops["start"], t0, t1)
        e = np.clip(ops["start"] + ops["dur"], t0, t1)
        module = _module_of(plane, ops["start"])
        mods = sorted(set(module))
        key = np.searchsorted(mods, module) * len(ops["names"]) + ops["id"]
        per_key = np.bincount(key, weights=e - s,
                              minlength=len(mods) * len(ops["names"]))
        for k in np.flatnonzero(per_key > 0):
            m, i = divmod(int(k), len(ops["names"]))
            if CONTAINER.search(ops["names"][i]):
                continue
            name = f"{mods[m]}/{short_name(ops['names'][i])}"
            ops_s[name] = ops_s.get(name, 0.0) + float(per_key[k])
        for label, ns in attribute(gaps(busy(plane), t0, t1), timeline,
                                   labels).items():
            idle[label] = idle.get(label, 0.0) + ns
    n = max(1, len(planes))

    def rank(table):
        items = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / n / 1e9] for k, v in items]

    return {"device_ops": rank(ops_s), "idle_gaps": rank(idle)}
