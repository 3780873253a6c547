"""Continuous-batching slot scheduler: per-slot decode positions.

Regression suite for the shared-position bug: the serve loop used to drive
every decode slot with one scalar `pos = slot_pos.max()`, so a slot whose
request was behind the longest one (shorter prompt, or admitted into a
freed slot mid-stream) wrote its KV cache at the wrong position and read
the previous occupant's stale rows. The fix: a [slots] pos vector into
`decode_step` (per-slot cache writes + per-slot valid lengths) and zeroing
a slot's cache lanes on admission. The pinned property: batched
mixed-length outputs are token-for-token identical to serving each request
alone.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_host_mesh
from repro.launch.serve import _is_axes, run_lm_server, zero_slot
from repro.models.registry import get_model

#: mixed prompt lengths + more requests than slots forces BOTH failure
#: modes of the old code: lagging slots (unequal lengths) and slot reuse
#: (request 3+ lands in a lane holding a finished request's cache)
PROMPT_LENS = (5, 9, 3, 7)
GEN = 3
SLOTS = 2


def _prompts(vocab):
    rng = np.random.default_rng(0)
    return [
        rng.integers(0, vocab, size=n).astype(np.int32).tolist()
        for n in PROMPT_LENS
    ]


@pytest.mark.parametrize(
    "arch",
    [
        "gemma-2b",  # decoder: per-slot KV writes + valid lengths
        "mamba2-130m",  # ssm: position-free, but state zeroing on reuse
        "zamba2-2.7b",  # hybrid: per-slot KV AND ssm/conv state zeroing
    ],
)
def test_mixed_length_batched_matches_single(arch):
    model = get_model(arch, smoke=True)
    vocab = model.cfg.vocab if hasattr(model.cfg, "vocab") else model.cfg.lm.vocab
    prompts = _prompts(vocab)
    cache_len = max(PROMPT_LENS) + GEN
    with jax.set_mesh(make_host_mesh()):
        batched, _ = run_lm_server(model, prompts, GEN, SLOTS, cache_len)
        singles = [
            run_lm_server(model, [p], GEN, slots=1, cache_len=cache_len)[0][0]
            for p in prompts
        ]
    assert batched == singles, (
        f"{arch}: batched continuous-batching outputs diverged from "
        f"single-request decoding: {batched} vs {singles}"
    )


def test_decode_step_vector_pos_matches_scalar():
    """decode_step with a [B] pos vector of one shared value must agree
    with the legacy scalar pos (the lockstep special case)."""
    model = get_model("gemma-2b", smoke=True)
    with jax.set_mesh(make_host_mesh()):
        params = model.init_params(jax.random.PRNGKey(0))
        shapes = model.init_cache_shape(2, 8)
        cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )
        toks = jnp.asarray([[3], [5]], jnp.int32)
        scalar_logits, scalar_cache = model.decode_step(
            params, cache, {"tokens": toks, "pos": jnp.asarray(2, jnp.int32)}
        )
        vec_logits, vec_cache = model.decode_step(
            params, cache, {"tokens": toks,
                            "pos": jnp.asarray([2, 2], jnp.int32)}
        )
        np.testing.assert_array_equal(
            np.asarray(scalar_logits), np.asarray(vec_logits)
        )
        for a, b in zip(jax.tree.leaves(scalar_cache),
                        jax.tree.leaves(vec_cache)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_zero_slot_clears_only_that_lane():
    model = get_model("zamba2-2.7b", smoke=True)  # kv + ssm + conv leaves
    with jax.set_mesh(make_host_mesh()):
        logical = model.cache_logical()
        shapes = model.init_cache_shape(3, 6)
        cache = jax.tree.map(
            lambda s: jnp.ones(s.shape, s.dtype), shapes,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )
        wiped = zero_slot(cache, logical, 1)
        for arr, axes in zip(
            jax.tree.leaves(wiped),
            jax.tree.leaves(logical, is_leaf=_is_axes),
        ):
            b = axes.index("batch")
            arr = np.moveaxis(np.asarray(arr, np.float32), b, 0)
            assert (arr[1] == 0).all()  # the admitted slot is clean
            assert (arr[0] == 1).all() and (arr[2] == 1).all()  # others kept
