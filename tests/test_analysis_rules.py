"""Planted violations must trip their named analysis rules, and the clean
committed tree must pass (tests for src/repro/analysis + check_analysis.py,
mirroring test_bench_gate.py's synthetic-trip style).

Note: repro.analysis (this subsystem) is distinct from repro.launch.analysis
(the HLO cost analyzer, covered by tests/test_analysis.py).
"""

import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.lint import Linter, run_lint
from repro.analysis.report import (
    SCHEMA,
    Finding,
    evaluate,
    load_baseline,
    make_report,
)
from repro.analysis.trace_audit import (
    audit_donation,
    audit_jaxpr,
    audit_shape_cache,
)

REPO = Path(__file__).resolve().parents[1]

sys.path.insert(0, str(Path(__file__).resolve().parent))
import check_analysis  # noqa: E402


def lint_source(code: str):
    """Lint a synthetic module; return the list of tripped rule names."""
    src = textwrap.dedent(code)
    findings = Linter(REPO / "src/repro/_planted.py", REPO, source=src).run()
    return findings


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# lint: non-atomic-artifact-write
# ---------------------------------------------------------------------------

def test_planted_bare_savez_trips():
    findings = lint_source("""
        import numpy as np

        def save(path, arrays):
            np.savez(path, **arrays)
    """)
    assert rules_of(findings) == ["non-atomic-artifact-write"]
    assert findings[0].context == "save"


def test_planted_bare_open_w_and_json_dump_trip():
    findings = lint_source("""
        import json

        def save(path, payload):
            with open(path, "w") as f:
                json.dump(payload, f)
    """)
    # both the open(..., "w") and the json.dump into its bare handle trip
    assert rules_of(findings) == ["non-atomic-artifact-write"]
    assert len(findings) == 2


def test_planted_write_text_trips():
    findings = lint_source("""
        def save(path, text):
            path.write_text(text)
    """)
    assert rules_of(findings) == ["non-atomic-artifact-write"]


def test_atomic_write_handle_is_clean():
    findings = lint_source("""
        import json
        import numpy as np
        from repro.ioutils import atomic_write

        def save(path, payload, arrays):
            with atomic_write(path, "w") as f:
                json.dump(payload, f)
            with atomic_write(path, "wb") as g:
                np.savez(g, **arrays)
    """)
    assert findings == []


def test_read_mode_open_is_clean():
    findings = lint_source("""
        import json

        def load(path):
            with open(path) as f:
                return json.load(f)
    """)
    assert findings == []


# ---------------------------------------------------------------------------
# lint: traced-context rules
# ---------------------------------------------------------------------------

def test_planted_host_sync_item_under_trace_trips():
    findings = lint_source("""
        import jax

        @jax.jit
        def step(x):
            return x + x.sum().item()
    """)
    assert rules_of(findings) == ["host-sync-under-trace"]


def test_planted_float_of_traced_param_trips():
    findings = lint_source("""
        import jax

        def run(x0):
            def body(x):
                return x * float(x0)

            def cond(x):
                return x.sum() > 0

            return jax.lax.while_loop(cond, body, x0)
    """)
    # body/cond are traced via while_loop; float(x0)... x0 is run's param,
    # not body's — only flagged when the converted name is a TRACED param
    # of the flagged function itself, so this is clean...
    # ...but the same conversion of body's own parameter must trip:
    findings2 = lint_source("""
        import jax

        def run(x0):
            def body(x):
                return x * float(x)

            def cond(x):
                return x.sum() > 0

            return jax.lax.while_loop(cond, body, x0)
    """)
    assert "host-sync-under-trace" not in rules_of(findings)
    assert rules_of(findings2) == ["host-sync-under-trace"]


def test_host_sync_outside_trace_is_clean():
    findings = lint_source("""
        def harvest(out):
            return float(out.sum()), out.n_accepted.item()
    """)
    assert findings == []


def test_static_argname_param_is_clean():
    findings = lint_source("""
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("tile",))
        def kernel(x, *, tile):
            pad = int(tile) * 2
            return x[:pad]
    """)
    assert findings == []


def test_planted_numpy_rng_under_trace_trips():
    findings = lint_source("""
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x + np.random.normal()
    """)
    assert rules_of(findings) == ["python-rng-under-trace"]


def test_planted_time_under_trace_trips():
    findings = lint_source("""
        import time
        import jax

        @jax.jit
        def step(x):
            t0 = time.time()
            return x * t0
    """)
    assert rules_of(findings) == ["time-under-trace"]


def test_time_on_host_is_clean():
    findings = lint_source("""
        import time

        def bench(fn, x):
            t0 = time.perf_counter()
            fn(x)
            return time.perf_counter() - t0
    """)
    assert findings == []


def test_planted_scalar_closure_capture_trips():
    """The silent in-jit tile clamp bug class: a factory bakes
    float(parameter) into a jitted closure as a compile constant."""
    findings = lint_source("""
        import jax

        def make_step(scale_arg):
            scale = float(scale_arg)

            def step(x):
                return x * scale

            return jax.jit(step)
    """)
    assert rules_of(findings) == ["scalar-closure-capture"]
    assert findings[0].context == "step"


def test_literal_closure_constant_is_clean():
    """Deliberate literal statics stay allowed — only param-derived
    conversions trip."""
    findings = lint_source("""
        import jax

        def make_step():
            scale = 3.0

            def step(x):
                return x * scale

            return jax.jit(step)
    """)
    assert findings == []


def test_transitive_same_module_callee_is_traced():
    findings = lint_source("""
        import time
        import jax

        def helper(x):
            return x * time.time()

        @jax.jit
        def step(x):
            return helper(x)
    """)
    assert rules_of(findings) == ["time-under-trace"]


# ---------------------------------------------------------------------------
# lint: suppression machinery
# ---------------------------------------------------------------------------

def test_suppression_with_reason_suppresses():
    findings = lint_source("""
        import numpy as np

        def save(tmp, arr):
            # analysis: allow(non-atomic-artifact-write) — staged into an
            # uncommitted tmp dir; the directory rename is the atomic commit
            np.savez(tmp, arr=arr)
    """)
    assert findings == []


def test_suppression_without_reason_trips_its_own_rule():
    findings = lint_source("""
        import numpy as np

        def save(tmp, arr):
            # analysis: allow(non-atomic-artifact-write)
            np.savez(tmp, arr=arr)
    """)
    assert rules_of(findings) == ["suppression-missing-reason"]


def test_suppression_for_other_rule_does_not_suppress():
    findings = lint_source("""
        import numpy as np

        def save(path, arr):
            # analysis: allow(time-under-trace) — wrong rule on purpose
            np.savez(path, arr=arr)
    """)
    assert rules_of(findings) == ["non-atomic-artifact-write"]


# ---------------------------------------------------------------------------
# trace audit: planted jaxpr violations
# ---------------------------------------------------------------------------

def test_planted_f64_promotion_trips():
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) * 2
        )(jnp.zeros(4, jnp.float32))
    findings = audit_jaxpr(jaxpr, "planted/f64")
    assert "f64-promotion" in rules_of(findings)


def test_planted_f64_inside_scan_trips():
    """The walker must recurse into control-flow sub-jaxprs."""
    with jax.enable_x64(True):
        def body(c, x):
            return c, x.astype(jnp.float64).sum()

        jaxpr = jax.make_jaxpr(
            lambda xs: jax.lax.scan(body, 0.0, xs)
        )(jnp.zeros((3, 2), jnp.float32))
    findings = audit_jaxpr(jaxpr, "planted/f64-scan")
    assert "f64-promotion" in rules_of(findings)


def test_planted_weak_type_leak_trips():
    jaxpr = jax.make_jaxpr(lambda x: (x, jnp.sin(2.0)))(
        jnp.zeros(3, jnp.float32)
    )
    findings = audit_jaxpr(jaxpr, "planted/weak")
    assert "weak-type-leak" in rules_of(findings)


def test_planted_host_callback_trips():
    def fn(x):
        y = jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x
        )
        return y * 2

    jaxpr = jax.make_jaxpr(fn)(jnp.zeros(3, jnp.float32))
    findings = audit_jaxpr(jaxpr, "planted/callback")
    assert "host-transfer-under-jit" in rules_of(findings)


def test_clean_f32_jaxpr_passes():
    jaxpr = jax.make_jaxpr(
        lambda x: jnp.sin(x) + jnp.float32(1.0)
    )(jnp.zeros(4, jnp.float32))
    assert audit_jaxpr(jaxpr, "clean") == []


def test_planted_shape_cache_recompile_trips():
    a = {"obs": jnp.zeros((3, 21)), "pop": jnp.float32(1e6)}
    b = {"obs": jnp.zeros((3, 28)), "pop": jnp.float32(5e6)}  # shape drift
    findings = audit_shape_cache([a, b], "planted/retrace")
    assert rules_of(findings) == ["shape-cache-retrace"]


def test_same_shape_variants_share_one_compile():
    a = {"obs": jnp.zeros((3, 21)), "pop": jnp.float32(1e6)}
    b = {"obs": jnp.ones((3, 21)), "pop": jnp.float32(5e6)}  # values only
    assert audit_shape_cache([a, b], "clean/retrace") == []


def test_planted_non_donated_buffer_trips():
    def loop(buf, x):
        return buf + x

    buf = jnp.zeros((256, 4), jnp.float32)
    x = jnp.ones((256, 4), jnp.float32)
    text_plain = jax.jit(loop).lower(buf, x).as_text()
    findings = audit_donation(
        text_plain, "planted/donation", expected_donated=(0,)
    )
    assert rules_of(findings) == ["non-donated-buffer"]

    text_donated = jax.jit(loop, donate_argnums=(0,)).lower(buf, x).as_text()
    assert audit_donation(
        text_donated, "clean/donation", expected_donated=(0,)
    ) == []


# ---------------------------------------------------------------------------
# the gate decision (pure) + report schema
# ---------------------------------------------------------------------------

def _finding(rule="non-atomic-artifact-write", ctx="save"):
    return Finding(rule=rule, path="src/repro/x.py", line=3, context=ctx,
                   message="planted")


def test_gate_fails_on_unbaselined_finding(capsys):
    assert evaluate(set(), [_finding()]) == 1


def test_gate_passes_on_baselined_finding():
    f = _finding()
    assert evaluate({f.key}, [f]) == 0


def test_gate_fails_on_stale_baseline_entry():
    assert evaluate({"time-under-trace:src/repro/gone.py:fn"}, []) == 1


def test_gate_passes_clean():
    assert evaluate(set(), []) == 0


def test_report_schema_and_keys(tmp_path):
    f = _finding()
    report = make_report([f], ["lint"])
    assert report["schema"] == SCHEMA == "analysis-report/v1"
    assert report["counts"] == {
        "total": 1, "by_rule": {"non-atomic-artifact-write": 1}
    }
    assert report["findings"][0]["key"] == f.key
    # baseline round-trip: a key written to the baseline file matches
    b = tmp_path / "baseline.txt"
    b.write_text(f"# comment\n{f.key}\n")
    assert load_baseline(b) == {f.key}
    assert load_baseline(tmp_path / "missing.txt") == set()


# ---------------------------------------------------------------------------
# the committed tree is clean
# ---------------------------------------------------------------------------

def test_committed_tree_lints_clean():
    """The acceptance criterion for the lint half: zero unbaselined findings
    on the real repo (suppressions with reasons are already applied)."""
    findings = run_lint(REPO)
    known = load_baseline(REPO / "tests" / "analysis_baseline.txt")
    new = [f for f in findings if f.key not in known]
    assert new == [], "\n".join(str(f) for f in new)


@pytest.mark.slow
def test_committed_tree_audits_clean_quick():
    """Axis-coverage trace audit of the real wave loops stays clean (the
    full cross product runs in the repro-lint CI job / nightly)."""
    from repro.analysis.trace_audit import run_audit

    findings = run_audit(quick=True)
    known = load_baseline(REPO / "tests" / "analysis_baseline.txt")
    new = [f for f in findings if f.key not in known]
    assert new == [], "\n".join(str(f) for f in new)


def test_check_analysis_cli_lint_pass_on_committed_tree():
    """The gate entry point itself returns 0 for the lint pass."""
    assert check_analysis.main(["--pass", "lint"]) == 0
