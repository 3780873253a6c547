"""Spatial metapopulation SEIR: coupled SEIR patches with a mobility matrix.

Four compartments [S, E, I, R] per region and four shared parameters
[beta, sigma, gamma, kappa]. The exposure hazard in region r uses the
mobility-weighted infectious mass instead of the local I:

  S_r -> E_r   beta * S_r * (sum_q M[r, q] * I_q) / P_r
  E_r -> I_r   sigma * E_r
  I_r -> R_r   gamma * I_r

M is the row-stochastic mobility matrix (`CompartmentalModel.mobility`);
row r says where region r's contacts happen. The coupled infectious mass
arrives as an EXTRA state row appended after the local compartments —
declared by `coupled=("I",)` on the spec — so this hazard body stays
row-level and lowers unchanged to the XLA engine (trailing region axis)
and the Pallas kernel (per-region VREG rows).

With M = I (identity mobility) each region is an independent SEIR patch of
population P/R — the invariant pinned by tests/test_metapop.py. The
registered default is R=4 on a ring (each region keeps 90% of contacts
local, 5% to each ring neighbour); `repro.epi.spec.regionalize` rescales
it to any R (the 100-region campaign example in the README).

Populations and seeding: without a geography, region `seed_region` (0)
receives the dataset's day-0 counts exactly as single-region SEIR does, and
every region holds P/R, the others fully susceptible. A dataset with a
geography (the bundled `usa_states`: 51 regions, per-state populations and
day-0 counts, a gravity mobility matrix) sets P_r and the seeds of every
region, and its matrix replaces M unless the run's config sets one. Region
r's contacts in q then meet q's prevalence, and the exposure hazard reads

  S_r -> E_r   beta * S_r * sum_q M[r, q] * I_q / P_q

(the engine hands this body the coupled row P_r * sum_q M[r, q] I_q / P_q,
`engine.coupling_matrix`); with every P_q = P/R the two forms agree.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.epi.models import register
from repro.epi.spec import CompartmentalModel, make_mobility


def _hazard_rows(sc, pc, population):
    s, e, i, _r, i_eff = sc  # i_eff = mobility-weighted I (coupled row)
    beta, sigma, gamma, _kappa = pc
    return (
        beta * s * i_eff / population,  # S -> E (coupled exposure)
        sigma * e,  # E -> I
        gamma * i,  # I -> R
    )


def _initial_rows(pc, population, a0, r0, _d0):
    kappa = pc[3]
    e0 = kappa * a0
    zeros = jnp.zeros_like(a0) * kappa
    i0 = zeros + a0
    s0 = population - (e0 + a0 + r0)
    return (s0, e0, i0, zeros + r0)


N_REGIONS = 4

MODEL = register(
    CompartmentalModel(
        name="metapop_seir",
        compartments=("S", "E", "I", "R"),
        param_names=("beta", "sigma", "gamma", "kappa"),
        prior_highs=(2.0, 1.0, 1.0, 2.0),
        stoichiometry=(
            # S   E   I   R
            (-1, +1, 0, 0),  # S -> E
            (0, -1, +1, 0),  # E -> I
            (0, 0, -1, +1),  # I -> R
        ),
        observed=("I", "R"),
        hazard_rows=_hazard_rows,
        initial_rows=_initial_rows,
        default_theta=(0.6, 0.3, 0.2, 1.0),
        n_regions=N_REGIONS,
        mobility=make_mobility("ring:0.1", N_REGIONS),
        coupled=("I",),
        doc="4-region metapopulation SEIR on a ring (10% mobility leakage).",
    )
)
