"""Plain reference of the paper's SIARD model (arXiv:2012.14332, section 2.1).

Written from the paper's equations, independent of the program:

  compartments  S, I, A, R, D, Ru          observed  A, R, D
  theta         alpha0, alpha, n, beta, gamma, delta, eta, kappa
  g             alpha0 + alpha / (1 + (A + R + D)^n)
  transitions   S->I  g*S*I/P,  I->A  gamma*I,  A->R  beta*A,
                A->D  delta*A,  I->Ru beta*eta*I   (clamped in this order)
  day 0         I = kappa*A0, Ru = 0, S = P - (A0 + R0 + D0 + I)

Rows are [B, R] arrays (R = 1 here); `dtype` is the precision the
reference computes in.
"""

import jax.numpy as jnp

COMPARTMENTS = ("S", "I", "A", "R", "D", "Ru")
#: (source, destination) compartment indices, in clamp order
TRANSITIONS = ((0, 1), (1, 2), (2, 3), (2, 4), (1, 5))
OBSERVED = (2, 3, 4)


def initial(theta, population, a0, r0, d0, n_regions, dtype):
    kappa = theta[:, 7:8]
    zeros = jnp.zeros((theta.shape[0], n_regions), dtype)
    i0 = kappa * a0 + zeros
    s0 = population - (a0 + r0 + d0 + i0)
    return [s0, i0, zeros + a0, zeros + r0, zeros + d0, zeros]


def hazards(rows, theta, population, mobility, dtype):
    s, i, a, r, d, _ru = rows
    alpha0, alpha, n, beta, gamma, delta, eta = (
        theta[:, k:k + 1] for k in range(7))
    g = alpha0 + alpha / (1.0 + jnp.power(jnp.maximum(a + r + d, 0.0), n))
    return [g * s * i / population, gamma * i, beta * a, delta * a,
            beta * eta * i]
