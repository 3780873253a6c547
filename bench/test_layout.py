"""The benchmark's layout, its discovery by name, the end-to-end arithmetic
and the result line. Nothing here starts the chip path."""

import json
import math
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np

from bench import compare, harness

ROOT = Path(__file__).resolve().parents[1]
BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text \
        and "\t" not in text


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_names_units_and_lines():
    names = []
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[key]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((key, entry["name"]))
    assert len(set(names)) == len(names)
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        assert _line(w["why"]) and w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert _line(c["why"]) and _line(c["source"])


def test_every_cell_finds_its_files_by_name():
    used = set()
    for w in BENCH["workloads"]:
        cell = harness.load_cell(BENCH, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["name"] == w["traffic"]
        assert set(cell.limits) == {"eps_gap", "dist_gap", "set_diff",
                                    "stop_gap", "short_fits", "over_eps",
                                    "sims_mismatch"}
        assert (ROOT / "bench" / "configs" / cell.config["reference"]).exists()
        used.add(w["config"])
    assert used == {c["name"] for c in BENCH["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_config_files_state_their_cut():
    for c in BENCH["configs"]:
        path = ROOT / c["file"]
        assert path.parts[len(ROOT.parts)] == "bench"
        data = json.loads(path.read_text())
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]
        assert "assumed" in data and data["dtype"] == "float32"
        assert data.get("chips", 1) == {w["chips"] for w in BENCH["workloads"]
                                        if w["config"] == c["name"]}.pop()
        assert len(c["reduced"]) <= 16


def test_every_metric_has_a_reader():
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
        assert callable(harness.reader("e2e", m["name"]))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert callable(harness.reader("layers", m["name"]))
        assert _line(m["layer"]) and m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert m["moves"] in {x["name"] for x in
                                  harness.metrics_of(BENCH, cell, "end_to_end")}


def test_every_cell_reports_enough():
    for w in BENCH["workloads"]:
        e2e = {m["name"] for m in harness.metrics_of(BENCH, w["name"],
                                                     "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_of(BENCH, w["name"], "per_layer")


def test_four_chip_cells_and_check_time():
    cells = BENCH["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    runs = 2 + 14 * 24
    need = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert need <= 43200


def test_peak_table_is_keyed_by_device_kind():
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert "TPU v5" in peaks["source"] or "v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_files_under_paths_are_named_from_name_characters():
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in (ROOT / "bench").rglob("*"):
        if "__pycache__" in p.parts:
            continue
        assert allowed.match(str(p.relative_to(ROOT))), p


class _Fit:
    def __init__(self, waves, latency):
        self.waves, self.latency = waves, latency


class _Window:
    def __init__(self, fits, seconds):
        self.fits, self.seconds, self.wave_size = fits, seconds, 100_000
        self.setup_s = 12.5


def test_rate_is_all_work_over_all_the_window():
    fits = [_Fit(w, 0.1) for w in (9, 10, 11, 10)]
    value = harness.reader("e2e", "sims_per_s")(_Window(fits, 2.0))
    assert value == 40 * 100_000 / 2.0


def test_median_is_over_every_fit():
    lat = list(np.random.default_rng(50).gamma(2.0, 0.1, size=137))
    fits = [_Fit(10, x) for x in lat]
    value = harness.reader("e2e", "fit_p50_s")(_Window(fits, 14.0))
    assert math.isclose(value, float(np.percentile(lat, 50)), rel_tol=1e-12)


def test_setup_is_read_as_measured():
    assert harness.reader("e2e", "setup_s")(_Window([], 1.0)) == 12.5


def test_pilot_and_check_sizes_follow_the_traffic():
    deep = harness.load_cell(BENCH, "siard3.deep")
    assert math.isclose(deep.quantile, 100 / (10.1 * 100_000))
    assert deep.n_pilot == 4_100_000  # 41 waves: eps near the 400th
    four = harness.load_cell(BENCH, "siard3.deep.chips4")
    assert four.wave_size == 400_000 and four.n_pilot == 16_400_000


def test_sample_holds_the_longest_fit_and_follows_the_seed():
    fits = [_Fit(w, 0.1) for w in (9, 10, 14, 10, 11, 9, 10)]
    a = harness.sample_fits(fits, 3, 2**31 + 5)
    b = harness.sample_fits(fits, 3, 2**31 + 5)
    assert a[0] is fits[2] and [id(f) for f in a] == [id(f) for f in b]
    assert len({id(f) for f in a}) == 3


def test_judge_lists_every_number_beside_its_limit():
    ok, table = compare.judge({"a": 0.5, "b": 2.0}, {"a": 1.0, "b": 1.0})
    assert not ok and table == {"a": {"value": 0.5, "limit": 1.0},
                                "b": {"value": 2.0, "limit": 1.0}}
    ok, _ = compare.judge({"a": 0.0}, {"a": 0.0, "missing": 1.0})
    assert not ok


def _run_py(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "siard3.deep",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run_py(ROOT, {"PYTHONPATH": str(ROOT / "src")})
    assert p.returncode != 0
    assert "{" not in p.stdout and "TPU" in p.stderr.upper()


def test_bare_checkout_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0 and "{" not in p.stdout
