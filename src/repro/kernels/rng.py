"""Counter-based RNG primitive shared by the Pallas kernel and its oracle.

The IPU samples Normal(h, sqrt(h)) on-tile; the TPU-native analogue is a
stateless counter-based generator evaluated inside the kernel, so the noise
tensor [B, T, 5] never exists in HBM. We use a murmur3-finalizer double-mix
hash on (seed, sample-index, counter) -> uint32 -> Box-Muller. It is NOT
crypto-grade but passes the statistical checks in tests/test_rng.py
(moments, uniformity, lag correlation). On real TPU hardware the production
alternative is `pltpu.prng_random_bits`; the hash path is kept because it is
bit-reproducible across CPU interpret mode and TPU, which is what makes the
kernel-vs-oracle tests exact and ABC runs replayable across backends.

All functions operate on uint32 arrays of any shape and are pure jnp, so the
same code runs inside a Pallas kernel body and in the pure-jnp oracle.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_P1 = np.uint32(0x9E3779B1)  # golden-ratio prime — sample index stream
_P2 = np.uint32(0x85EBCA77)  # counter stream
_X1 = np.uint32(0x1B873593)  # second-round decorrelation constant

_TWO_PI = np.float32(2.0 * np.pi)
_INV_2_24 = np.float32(1.0 / (1 << 24))


def fmix32(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 32-bit finalizer (bijective mix)."""
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 13)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


def hash_u32(seed: jnp.ndarray, idx: jnp.ndarray, ctr) -> jnp.ndarray:
    """Counter-based uint32 stream: h(seed, sample-idx, counter)."""
    ctr = jnp.asarray(ctr, jnp.uint32)
    h = (
        jnp.asarray(seed, jnp.uint32)
        ^ (jnp.asarray(idx, jnp.uint32) * _P1)
        ^ (ctr * _P2)
    )
    return fmix32(fmix32(h ^ _X1))


def uniform_open(seed, idx, ctr) -> jnp.ndarray:
    """U in (0, 1]: ((h >> 8) + 1) * 2^-24 — log-safe."""
    h = hash_u32(seed, idx, ctr)
    # <= 2^24 fits int32 exactly; the TPU kernel compiler has no
    # uint32 -> float32 cast, so convert through int32 (same value)
    u = ((h >> np.uint32(8)) + np.uint32(1)).astype(jnp.int32)
    return u.astype(jnp.float32) * _INV_2_24


def normal(seed, idx, ctr) -> jnp.ndarray:
    """Standard normal via Box–Muller (cos branch).

    Consumes counters (2*ctr, 2*ctr + 1) of the (seed, idx) stream.
    """
    ctr = jnp.asarray(ctr, jnp.uint32)
    two = np.uint32(2)
    one = np.uint32(1)
    u1 = uniform_open(seed, idx, ctr * two)
    u2 = uniform_open(seed, idx, ctr * two + one)
    r = jnp.sqrt(np.float32(-2.0) * jnp.log(u1))
    return r * jnp.cos(_TWO_PI * u2)


def day_transition_ctr(day, k, slots: int = 8) -> jnp.ndarray:
    """Counter layout: `slots` transition slots per day (8 by default, 5
    used by the paper's SIARD). Metapop models widen to
    `CompartmentalModel.ctr_slots` (the next multiple of 8 above
    R * n_transitions, flattened region-major: slot r * n_transitions + k);
    at R=1 that is exactly 8, so single-region streams are unchanged."""
    return jnp.asarray(day, jnp.uint32) * np.uint32(slots) + jnp.asarray(k, jnp.uint32)
