"""Plain reference of the 51-region metapopulation SEIR of the USA's states.

Written from the model's equations, independent of the program:

  compartments  S, E, I, R in each region r      observed  I, R of each region
  theta         beta, sigma, gamma, kappa, shared by the regions
  force         J_r = sum over q of M[r, q] * I_q / P_q, the prevalence
                where region r's contacts happen
  transitions   S->E  beta*S*J,  E->I  sigma*E,  I->R  gamma*I
                (clamped in this order)
  day 0         E = kappa*A0_r, I = A0_r, R = R0_r, S = P_r - (E + I + R)

Everything about the regions comes from the configuration's own
`geography` block, and nothing from the dataset the harness hands over:
P_r is its `population`; A0_r and R0_r split its `a0_total` and `r0_total`
over the regions in proportion to population by largest remainder (ties to
the earlier region); M is the gravity matrix

  M[i][i] = 1 - eps
  M[i][j] = eps * (P_j^b / d_ij^c) / sum over k != i of (P_k^b / d_ik^c)

with d the haversine distance between the regions' capitals, computed in
float64 and rounded once to float32. The harness hands `initial` the
dataset's populations and day-0 counts, and `hazards` an identity matrix
(the configuration says `"mobility": null`); this module ignores all of
them. The force is computed at `jax.default_matmul_precision("highest")`: a
TPU otherwise runs a float32 product in one bfloat16 pass.

Rows are [B, R] arrays; `dtype` is the precision the reference computes in.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

COMPARTMENTS = ("S", "E", "I", "R")
#: (source, destination) compartment indices, in clamp order
TRANSITIONS = ((0, 1), (1, 2), (2, 3))
OBSERVED = (2, 3)

_GEOGRAPHY = json.loads(
    (Path(__file__).with_name("metapop_usa_states51.json")).read_text()
)["geography"]


def gravity(geo: dict) -> np.ndarray:
    """[R, R] float32 gravity matrix of a `geography` block."""
    pop = np.asarray(geo["population"], np.float64)
    lat, lon = np.radians(np.asarray(geo["capital_lat_lon"], np.float64)).T
    n = pop.size
    m = np.zeros((n, n), np.float64)
    for i in range(n):
        w = np.zeros(n, np.float64)
        for j in range(n):
            if j == i:
                continue
            h = (np.sin((lat[i] - lat[j]) / 2.0) ** 2
                 + np.cos(lat[i]) * np.cos(lat[j])
                 * np.sin((lon[i] - lon[j]) / 2.0) ** 2)
            d = 2.0 * geo["earth_radius_km"] * np.arcsin(np.sqrt(h))
            w[j] = pop[j] ** geo["gravity_b"] / d ** geo["gravity_c"]
        m[i] = geo["gravity_eps"] * w / w.sum()
        m[i, i] = 1.0 - geo["gravity_eps"]
    return m.astype(np.float32)


def largest_remainder(total: int, pop) -> np.ndarray:
    """`total` whole counts in proportion to `pop`, by largest remainder;
    ties go to the earlier region."""
    whole = sum(pop)
    quota = [total * p for p in pop]
    counts = [q // whole for q in quota]
    left = total - sum(counts)
    order = sorted(range(len(pop)), key=lambda k: (-(quota[k] % whole), k))
    for k in order[:left]:
        counts[k] += 1
    return np.asarray(counts, np.float32)


MOBILITY = gravity(_GEOGRAPHY)
POPULATION = np.asarray(_GEOGRAPHY["population"], np.float32)
A0 = largest_remainder(_GEOGRAPHY["a0_total"], _GEOGRAPHY["population"])
R0 = largest_remainder(_GEOGRAPHY["r0_total"], _GEOGRAPHY["population"])


def initial(theta, population, a0, r0, d0, n_regions, dtype):
    """Day 0 from the geography block; the handed values are ignored."""
    kappa = theta[:, 3:4]
    zeros = jnp.zeros((theta.shape[0], n_regions), dtype)
    a0, r0 = jnp.asarray(A0, dtype), jnp.asarray(R0, dtype)
    e0 = kappa * a0 + zeros
    i0 = zeros + a0
    rec0 = zeros + r0
    s0 = jnp.asarray(POPULATION, dtype) - (e0 + i0 + rec0)
    return [s0, e0, i0, rec0]


def hazards(rows, theta, population, mobility, dtype):
    """The day's rates; the handed population and matrix are ignored."""
    s, e, i, _r = rows
    beta, sigma, gamma = (theta[:, k:k + 1] for k in range(3))
    prevalence = i / jnp.asarray(POPULATION, dtype)
    with jax.default_matmul_precision("highest"):
        force = jnp.matmul(prevalence, jnp.asarray(MOBILITY, dtype).T)
    return [beta * s * force, sigma * e, gamma * i]
