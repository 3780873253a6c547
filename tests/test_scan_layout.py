"""The fused day scan (`engine.simulate_observed_lowmem`) carries the state
as compartment rows.

  * its lowered scan body builds no array as wide as the state or the
    transitions, by `concatenate` or `reshape`, and gathers nothing: the
    state, the hazards and the observed channels are rows throughout (only
    the observed rows are stacked, into the distance);
  * the final state it returns equals the last day of `simulate`'s
    trajectory bit for bit, for every registered model, a 5-region
    geography and a 2-window schedule.

The compile of the paper-size loop for a described v5e, whose while body
must hold no `concatenate` of the `[B, C]` state or `[B, T]` hazards, is in
tests/test_chip_compile.py with the other compiles for the chip.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.epi import engine
from repro.epi.models import get_model, list_models
from repro.epi.spec import EpiModelConfig, InterventionSchedule, regionalize

B = 256
CFG = EpiModelConfig(population=1e6, num_days=12, a0=100.0, r0=5.0, d0=1.0)
R5_POP = (1.0e6, 2.5e5, 4.0e6, 7.5e5, 1.5e6)
R5_A0 = (50.0, 0.0, 20.0, 5.0, 0.0)
R5_R0 = (3.0, 0.0, 1.0, 0.0, 0.0)
R5_CFG = EpiModelConfig(population=R5_POP, num_days=12, a0=R5_A0, r0=R5_R0)


def _r5():
    return regionalize(get_model("metapop_seir"), 5, "ring:0.2")


def _functions(text: str) -> dict:
    """StableHLO function name -> its body text."""
    parts = re.split(r"\n\s*func\.func (?:public |private )?@", text)
    return {p.split("(", 1)[0]: p for p in parts[1:]}


def _while_body(text: str) -> str:
    """The `do` region of the module's one while loop (the day scan)."""
    start = text.index(" do {", text.index("stablehlo.while")) + len(" do {")
    depth = 1
    for i in range(start, len(text)):
        depth += {"{": 1, "}": -1}.get(text[i], 0)
        if depth == 0:
            return text[start:i]
    raise AssertionError("unbalanced while body")


def _scan_body(text: str) -> str:
    """The while body and every function it calls, transitively."""
    funcs = _functions(text)
    body = _while_body(text)
    seen, todo = set(), re.findall(r"call @([\w.\-]+)", body)
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        body += funcs[name]
        todo += re.findall(r"call @([\w.\-]+)", funcs[name])
    return body


def _built_widths(body: str) -> list:
    """(op, trailing shape) of each concatenate / reshape whose result has
    the batch as its leading axis."""
    out = []
    for op, dims in re.findall(
        r"stablehlo\.(concatenate|reshape)\b[^\n]*-> tensor<([\dx]+)x\w+>",
        body,
    ):
        shape = tuple(int(d) for d in dims.split("x"))
        if shape[0] == B:
            out.append((op, shape[1:]))
    return out


def _lowered_scan(model, cfg):
    theta = model.prior().sample(jax.random.PRNGKey(0), (B,))
    obs = jnp.zeros((model.total_observed, cfg.num_days), jnp.float32)
    fn = jax.jit(lambda th, key, o: engine.simulate_observed_lowmem(
        model, th, key, cfg, o))
    return _scan_body(fn.lower(theta, jax.random.PRNGKey(1), obs).as_text())


@pytest.mark.parametrize("which", ["siard", "metapop_r5_geography"])
def test_scan_body_carries_rows(which):
    model = _r5() if which != "siard" else get_model("siard")
    cfg = R5_CFG if which != "siard" else CFG
    body = _lowered_scan(model, cfg)
    R, C, T = model.n_regions, model.n_state, model.n_transitions
    state_or_hazard = set()
    for n in (C, T):
        state_or_hazard |= {(n,), (R * n,)} | ({(R, n)} if R > 1 else set())
    assert "gather" not in body
    built = _built_widths(body)
    assert built, "the observed rows' stack into the distance should show"
    wide = [(op, s) for op, s in built if s in state_or_hazard]
    assert not wide, wide


def _final_state_cases():
    cases = [(name, "plain") for name in list_models()]
    cases.append(("metapop_seir", "r5_geography"))
    cases += [("siard", "two_windows"), ("metapop_seir", "two_windows")]
    return cases


@pytest.mark.parametrize("name,case", _final_state_cases())
def test_fused_final_state_equals_trajectory_end(name, case):
    model, cfg, schedule = get_model(name), CFG, None
    if case == "r5_geography":
        model, cfg = _r5(), R5_CFG
    theta = model.prior().sample(jax.random.PRNGKey(5), (64,))
    if case == "two_windows":
        schedule = InterventionSchedule.inferred(("beta",), (4, 9))
        scales = jax.random.uniform(jax.random.PRNGKey(6), (64, 2), maxval=1.5)
        theta = jnp.concatenate([theta, scales], axis=-1)
    key = jax.random.PRNGKey(7)
    traj = engine.simulate(model, theta, key, cfg, schedule)
    observed = engine.simulate_observed(model, theta, key, cfg, schedule)[0]
    _, final = engine.simulate_observed_lowmem(
        model, theta, key, cfg, observed, schedule)
    assert final.shape == (64, model.total_state)
    np.testing.assert_array_equal(np.asarray(final), np.asarray(traj[:, -1, :]))
