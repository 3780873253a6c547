"""Jaxpr-level trace auditor over every registered ABC combination.

For each (model x backend x summary x distance x schedule-shape) combo this
pass abstractly traces the device-resident wave loop — `jax.make_jaxpr`
only, no XLA compile — and statically checks the contracts the campaign
runner and the paper's perf numbers rely on:

  shape-cache-retrace     two scenarios that the campaign `_ShapeCache`
                          maps to ONE key must present identical abstract
                          signatures (shape/dtype per leaf) to the jitted
                          loop; a mismatch means a silent recompile per
                          scenario. (pallas is the documented per-dataset
                          compile exception and is skipped.)
  f64-promotion           any convert_element_type to float64 (or any
                          float64 intermediate) in the loop — the whole
                          stack is f32 by contract; an f64 leak doubles
                          memory traffic and detunes the kernel.
  weak-type-leak          weakly-typed loop outputs: a Python-scalar
                          promotion escaping the loop re-specializes every
                          downstream consumer.
  host-transfer-under-jit callback/infeed/outfeed/debug primitives inside
                          the loop body — a hidden device->host round trip
                          per wave.
  non-donated-buffer      the wave runner's accept buffers (theta_buf,
                          dist_buf) must be donated to XLA, and no other
                          large input may go un-donated; checked on the
                          lowered MLIR of one representative runner per
                          (backend, schedule-shape).

All checks are static; the audit runs on CPU in seconds and never executes
a wave. The generic helpers (`audit_jaxpr`, `audit_shape_cache`,
`audit_donation`) are pure so the planted-violation tests can drive them
directly (tests/test_analysis_rules.py).
"""

from __future__ import annotations

import dataclasses
import itertools
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr
import numpy as np

from repro.analysis.report import Finding

AUDIT_RULES: Dict[str, str] = {
    "shape-cache-retrace": (
        "scenarios sharing a _ShapeCache key present different abstract "
        "signatures — the 'one compile per shape' contract is broken"
    ),
    "f64-promotion": (
        "float64 promotion inside a traced region (the stack is f32 by "
        "contract)"
    ),
    "weak-type-leak": (
        "weakly-typed output escapes a traced region and re-specializes "
        "downstream consumers"
    ),
    "host-transfer-under-jit": (
        "callback/infeed/outfeed/debug primitive inside a jitted region — "
        "a hidden device->host round trip per invocation"
    ),
    "non-donated-buffer": (
        "a buffer the wave-loop contract donates (or any large input) is "
        "not marked as donated in the lowered computation"
    ),
    "audit-trace-error": (
        "a registered combo failed to trace at all — it cannot compile "
        "either"
    ),
}

#: primitives that cross the device boundary from inside a trace
_HOST_PRIMS = {
    "pure_callback", "io_callback", "callback", "debug_callback",
    "debug_print", "infeed", "outfeed", "host_local_array_to_global_array",
}

#: donated-arg marker in jax 0.4.x StableHLO text
_DONATION_MARKER = "tf.aliasing_output"
_ARG_RE = re.compile(r"%arg(\d+):\s*tensor<([^>]*)>(\s*\{[^}]*\})?")


# ---------------------------------------------------------------------------
# generic, pure checkers (driven by run_audit AND the planted tests)
# ---------------------------------------------------------------------------

def _walk_jaxprs(jaxpr) -> Iterable:
    """Yield this jaxpr and every sub-jaxpr (scan/while/cond/pjit bodies)."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for val in eqn.params.values():
            for sub in jax.tree_util.tree_leaves(
                val, is_leaf=lambda x: isinstance(x, (Jaxpr, ClosedJaxpr))
            ):
                if isinstance(sub, ClosedJaxpr):
                    yield from _walk_jaxprs(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _walk_jaxprs(sub)


def audit_jaxpr(closed_jaxpr, context: str) -> List[Finding]:
    """f64 / weak-type / host-transfer checks on one traced computation."""
    findings: List[Finding] = []
    seen_rules = set()

    def emit(rule: str, message: str):
        # one finding per (rule, context): a single f64 leak fans out into
        # dozens of downstream f64 eqns — report the class once
        if rule in seen_rules:
            return
        seen_rules.add(rule)
        findings.append(Finding(
            rule=rule, path="-", line=0, context=context, message=message,
        ))

    jaxpr = closed_jaxpr.jaxpr if hasattr(closed_jaxpr, "jaxpr") else closed_jaxpr
    for sub in _walk_jaxprs(jaxpr):
        for eqn in sub.eqns:
            name = eqn.primitive.name
            if name in _HOST_PRIMS:
                emit(
                    "host-transfer-under-jit",
                    f"primitive {name!r} inside the traced region",
                )
            if name == "convert_element_type" and (
                eqn.params.get("new_dtype") == jnp.float64
            ):
                emit(
                    "f64-promotion",
                    "convert_element_type to float64 inside the traced "
                    "region",
                )
            for v in eqn.outvars:
                dtype = getattr(getattr(v, "aval", None), "dtype", None)
                if dtype == jnp.float64:
                    emit(
                        "f64-promotion",
                        f"primitive {name!r} produces a float64 intermediate",
                    )
    for v in jaxpr.outvars:
        aval = getattr(v, "aval", None)
        if getattr(aval, "weak_type", False):
            emit(
                "weak-type-leak",
                f"traced output {v} is weakly typed ({aval}) — a Python "
                "scalar promotion escapes the region",
            )
    return findings


def _signature(tree) -> List[Tuple]:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    sig = [(tuple(np.shape(x)), str(jnp.result_type(x))) for x in leaves]
    return [str(treedef)] + sig


def audit_shape_cache(variants: Sequence, context: str) -> List[Finding]:
    """Scenario variants meant to share ONE compile must present identical
    abstract signatures (pytree structure + per-leaf shape/dtype). Identical
    signatures guarantee jit-cache reuse; any mismatch is a silent
    per-scenario recompile."""
    findings: List[Finding] = []
    if not variants:
        return findings
    ref = _signature(variants[0])
    for i, v in enumerate(variants[1:], start=1):
        sig = _signature(v)
        if sig != ref:
            diff = [
                f"leaf {j}: {a} != {b}"
                for j, (a, b) in enumerate(zip(ref, sig)) if a != b
            ] or [f"tree arity {len(ref)} != {len(sig)}"]
            findings.append(Finding(
                rule="shape-cache-retrace", path="-", line=0,
                context=context,
                message=(
                    f"variant {i} changes the traced signature "
                    f"({'; '.join(diff[:3])}) — the wave loop recompiles "
                    "per scenario instead of once per shape"
                ),
            ))
    return findings


def audit_donation(
    lowered_text: str,
    context: str,
    expected_donated: Sequence[int] = (),
    large_threshold_bytes: int = 1 << 23,
) -> List[Finding]:
    """Check the lowered MLIR's entry signature for donation markers.

    `expected_donated` are flat argument indices that the calling contract
    donates (the wave runner's theta_buf/dist_buf); additionally any input
    of at least `large_threshold_bytes` must be donated or is flagged.
    """
    findings: List[Finding] = []
    header = lowered_text.split("func.func public @main", 1)
    if len(header) < 2:
        return [Finding(
            rule="non-donated-buffer", path="-", line=0, context=context,
            message="could not locate @main entry in lowered MLIR",
        )]
    sig = header[1].split("->", 1)[0]
    args: Dict[int, Tuple[int, bool]] = {}
    for m in _ARG_RE.finditer(sig):
        idx = int(m.group(1))
        shape_spec = m.group(2).split("x")
        nbytes, bits = 1, 32
        for part in shape_spec:
            if part.isdigit():
                nbytes *= int(part)
            elif part and part[0] in "fiu" and part[1:].isdigit():
                bits = int(part[1:])
        nbytes *= bits // 8
        donated = bool(m.group(3)) and _DONATION_MARKER in m.group(3)
        args[idx] = (nbytes, donated)
    for idx in expected_donated:
        if idx in args and not args[idx][1]:
            findings.append(Finding(
                rule="non-donated-buffer", path="-", line=0, context=context,
                message=(
                    f"arg {idx} is a wave-loop accept buffer the contract "
                    "donates (donate_argnums) but carries no "
                    f"{_DONATION_MARKER} marker — XLA double-buffers it"
                ),
            ))
    for idx, (nbytes, donated) in sorted(args.items()):
        if idx in expected_donated or donated:
            continue
        if nbytes >= large_threshold_bytes:
            findings.append(Finding(
                rule="non-donated-buffer", path="-", line=0, context=context,
                message=(
                    f"arg {idx} is {nbytes / 2**20:.1f} MiB and not donated "
                    "— consider donate_argnums if the caller discards it"
                ),
            ))
    return findings


# ---------------------------------------------------------------------------
# the registered-combination grid
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Combo:
    model: str
    backend: str
    summary: Optional[str]
    distance: str
    sched_shape: int  # number of intervention windows (0 = no schedule)
    #: regionalize the model to this R at audit time (1 = as registered;
    #: note metapop_seir is ALREADY 4-region as registered, so the model
    #: axis audits the regional path even at regions=1)
    regions: int = 1

    @property
    def tag(self) -> str:
        return (
            f"{self.model}/{self.backend}/{self.summary or 'identity'}/"
            f"{self.distance}/sched{self.sched_shape}"
            + (f"/r{self.regions}" if self.regions > 1 else "")
        )


def registered_combos(quick: bool = False) -> List[Combo]:
    """The full registered grid; `quick` covers every axis value while
    holding the others at defaults (axis coverage, not the cross product)."""
    from repro.core.summaries import DISTANCE_KINDS, list_summaries
    from repro.epi.models import list_models

    models = list(list_models())
    backends = ["xla", "xla_fused", "pallas"]
    summaries = [None] + [s for s in list_summaries() if s != "identity"]
    distances = list(DISTANCE_KINDS)
    sched_shapes = [0, 2]
    if not quick:
        full = [
            Combo(m, b, su, d, ss)
            for m, b, su, d, ss in itertools.product(
                models, backends, summaries, distances, sched_shapes
            )
        ]
        # region-axis column: audit regionalize-at-audit-time cells (a
        # coupled metapop and an uncoupled base model) across every backend
        # and both pooling modes — a bounded slice, not a full R axis of
        # the cross product
        full += [
            Combo(m, b, su, distances[0], ss, regions=3)
            for m in ("metapop_seir", "seir") if m in models
            for b in backends
            for su in (None, "region_pooled")
            for ss in sched_shapes
        ]
        return full
    base = Combo(models[0], "xla_fused", None, distances[0], 0)
    combos = {base}
    for m in models:
        combos.add(dataclasses.replace(base, model=m))
    for b in backends:
        combos.add(dataclasses.replace(base, backend=b))
    for su in summaries:
        combos.add(dataclasses.replace(base, summary=su))
    for d in distances:
        combos.add(dataclasses.replace(base, distance=d))
    for ss in sched_shapes:
        combos.add(dataclasses.replace(base, sched_shape=ss))
    # region-axis coverage: one coupled and one uncoupled regionalized cell
    if "metapop_seir" in models:
        combos.add(dataclasses.replace(
            base, model="metapop_seir", regions=3, summary="region_pooled"
        ))
    if "seir" in models:
        combos.add(dataclasses.replace(base, model="seir", regions=3))
    return sorted(combos, key=lambda c: c.tag)


def _resolve_spec(combo: Combo):
    """The combo's model spec, regionalized at audit time if regions > 1."""
    from repro.epi.models import get_model
    from repro.epi.spec import regionalize

    spec = get_model(combo.model)
    if combo.regions > 1:
        spec = regionalize(spec, combo.regions, "ring:0.1")
    return spec


def _schedule_for(shape: int, days: Sequence[int], spec):
    if shape == 0:
        return None
    from repro.epi.spec import InterventionSchedule

    return InterventionSchedule.inferred(
        (spec.param_names[0],), tuple(days[:shape])
    )


def _build_combo(combo: Combo, batch_size: int, num_days: int,
                 sched_days: Sequence[int] = (7, 14)):
    """(cfg, prior, dataset, loop, scenario-or-None) for one combo."""
    from repro.core.abc import (
        ABCConfig,
        build_wave_loop,
        make_parametric_simulator,
        make_simulator,
        scenario_data,
    )
    from repro.core.priors import schedule_prior
    from repro.epi.data import get_dataset

    spec = _resolve_spec(combo)
    cfg = ABCConfig(
        batch_size=batch_size,
        chunk_size=batch_size,
        num_days=num_days,
        backend=combo.backend,
        model=spec,
        summary=combo.summary,
        distance=combo.distance,
        schedule=_schedule_for(combo.sched_shape, sched_days, spec),
        wave_loop="device",
        interpret=True if combo.backend == "pallas" else None,
    )
    prior = schedule_prior(spec, cfg.schedule)
    dataset = get_dataset("synthetic_small", num_days, spec)
    if combo.backend == "pallas":
        sim = make_simulator(dataset, cfg)
        loop = build_wave_loop(prior, lambda th, k, _d: sim(th, k), cfg)
        data = None
    else:
        parametric = make_parametric_simulator(spec, cfg)
        loop = build_wave_loop(prior, parametric, cfg)
        data = scenario_data(dataset, cfg)
    return cfg, prior, dataset, loop, data


def _loop_args(cfg, prior, data):
    from repro.core.abc import wave_capacity

    cap = wave_capacity(cfg)
    th_buf = jnp.zeros((cap, prior.dim), jnp.float32)
    d_buf = jnp.full((cap,), jnp.inf, jnp.float32)
    key = jax.random.PRNGKey(0)
    return (
        key, jnp.int32(0), th_buf, d_buf, jnp.int32(0), jnp.int32(0),
        jnp.int32(1), jnp.float32(cfg.tolerance), data,
    )


def _scenario_variants(combo: Combo, cfg, num_days: int):
    """Two scenarios the campaign _ShapeCache maps to one key: a different
    dataset AND different breakpoint days of the same window count."""
    from repro.core.abc import scenario_data
    from repro.epi.data import get_dataset, synthetic_dataset

    spec = _resolve_spec(combo)
    ds_a = get_dataset("synthetic_small", num_days, spec)
    ds_b = synthetic_dataset(
        theta=spec.default_theta, population=5e6, num_days=num_days,
        a0=50.0, seed=11, name="audit_variant", model=spec,
    )
    variants = [scenario_data(ds_a, cfg), scenario_data(ds_b, cfg)]
    if combo.sched_shape:
        cfg_late = dataclasses.replace(
            cfg, schedule=_schedule_for(combo.sched_shape, (9, 19), spec)
        )
        variants.append(scenario_data(ds_a, cfg_late))
    return variants


def audit_combo(combo: Combo, batch_size: int = 1024, num_days: int = 21
                ) -> List[Finding]:
    """Trace one combo's wave loop and run every jaxpr-level check."""
    try:
        cfg, prior, dataset, loop, data = _build_combo(
            combo, batch_size, num_days
        )
        args = _loop_args(cfg, prior, data)
        jaxpr = jax.make_jaxpr(loop)(*args)
    except Exception as e:  # a combo that cannot trace cannot compile
        return [Finding(
            rule="audit-trace-error", path="-", line=0, context=combo.tag,
            message=f"{type(e).__name__}: {e}",
        )]
    findings = audit_jaxpr(jaxpr, combo.tag)
    if combo.backend != "pallas":
        # pallas bakes dataset scalars into the kernel: the documented
        # per-dataset compile exception (campaign._ShapeCache.key_of)
        findings.extend(audit_shape_cache(
            _scenario_variants(combo, cfg, num_days), combo.tag
        ))
    return findings


def audit_runner_donation(backend: str, sched_shape: int,
                          batch_size: int = 1024, num_days: int = 21
                          ) -> List[Finding]:
    """Lower one representative jitted wave runner per (backend, schedule
    shape) and verify the accept buffers carry donation markers. The
    donation setup lives in make_wave_runner/make_shardmap_runner and is
    combo-independent, so representatives cover the grid."""
    from repro.core.abc import ABCState, make_simulator, make_wave_runner
    from repro.core.priors import schedule_prior
    from repro.epi.data import get_dataset
    from repro.epi.models import get_model

    combo = Combo(
        model="siard", backend=backend, summary=None,
        distance="euclidean", sched_shape=sched_shape,
    )
    context = f"wave_runner/{backend}/sched{sched_shape}"
    try:
        cfg, prior, dataset, _, _ = _build_combo(combo, batch_size, num_days)
        sim = make_simulator(dataset, cfg)
        runner = make_wave_runner(prior, sim, cfg)
        state = ABCState(n_params=prior.dim)
        th_buf, d_buf, n0, fill0 = runner.init(state)
        lowered = runner.fn.lower(
            jax.random.PRNGKey(0), np.int32(0), th_buf, d_buf, n0, fill0,
            np.int32(1), np.float32(cfg.tolerance), None,
        )
        text = lowered.as_text()
    except Exception as e:
        return [Finding(
            rule="audit-trace-error", path="-", line=0, context=context,
            message=f"{type(e).__name__}: {e}",
        )]
    # flat args: key(1) + run_idx0 + theta_buf + dist_buf + ... — indices 2,3
    return audit_donation(text, context, expected_donated=(2, 3))


def run_audit(quick: bool = False, log=None) -> List[Finding]:
    findings: List[Finding] = []
    combos = registered_combos(quick=quick)
    for i, combo in enumerate(combos):
        if log and (i % 30 == 0 or i + 1 == len(combos)):
            log(f"[trace_audit] combo {i + 1}/{len(combos)}: {combo.tag}")
        findings.extend(audit_combo(combo))
    sched_shapes = [0, 2]
    backends = ["xla", "xla_fused"] if quick else ["xla", "xla_fused",
                                                   "pallas"]
    for backend in backends:
        for ss in sched_shapes:
            findings.extend(audit_runner_donation(backend, ss))
    return findings
