"""Integration: the production step builders actually EXECUTE on a sharded
mesh (8 host devices, 2x4 data x model), for train, prefill and decode."""

import pytest

from conftest import run_in_subprocess


@pytest.mark.slow
def test_train_prefill_decode_execute_sharded():
    out = run_in_subprocess(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.shapes import InputShape
from repro.launch.steps import build_train_step, build_prefill_step, build_decode_step
from repro.models.registry import get_model
from repro.optim import adamw_init

from repro.launch.mesh import make_auto_mesh
mesh = make_auto_mesh((2, 4), ("data", "model"))
results = []
with jax.set_mesh(mesh):
    for arch in ("gemma2-27b", "qwen3-moe-30b-a3b", "mamba2-130m"):
        model = get_model(arch, smoke=True)
        params = model.init_params(jax.random.PRNGKey(0))
        vocab = model.cfg.vocab if hasattr(model.cfg, "vocab") else model.cfg.lm.vocab

        # --- train step
        shape = InputShape("t", "train", 32, 4)
        built = build_train_step(model, mesh, shape, microbatch=2)
        opt = adamw_init(params)
        toks = jnp.asarray(np.arange(4 * 32).reshape(4, 32) % 7, jnp.int32)
        batch = {"tokens": toks, "labels": toks}
        params_s = jax.device_put(params, built.in_shardings[0])
        opt_s = jax.device_put(opt, built.in_shardings[1])
        batch_s = jax.device_put(batch, built.in_shardings[2])
        p2, o2, metrics = built.fn(params_s, opt_s, batch_s)
        loss = float(metrics["loss"])
        assert np.isfinite(loss), (arch, loss)

        # --- prefill
        shape_p = InputShape("p", "prefill", 32, 4)
        built_p = build_prefill_step(model, mesh, shape_p)
        params = model.init_params(jax.random.PRNGKey(0))  # p2 was donated
        spec, _ = model.make_inputs("prefill", 4, 32)
        concrete = {k: jnp.zeros(s.shape, s.dtype) + (1 if s.dtype == jnp.int32 else 0.1)
                    for k, s in spec.items()}
        params_p = jax.device_put(params, built_p.in_shardings[0])
        concrete = jax.device_put(concrete, built_p.in_shardings[1])
        logits = built_p.fn(params_p, concrete)
        assert logits.shape[-1] == vocab and logits.shape[1] == 1
        assert bool(jnp.all(jnp.isfinite(logits)))

        # --- decode (skip encdec-style extras; these 3 are decoder-like)
        shape_d = InputShape("d", "decode", 32, 4)
        built_d = build_decode_step(model, mesh, shape_d)
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             model.init_cache_shape(4, 32),
                             is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        dbatch = {"tokens": jnp.ones((4, 1), jnp.int32), "pos": jnp.asarray(0, jnp.int32)}
        params_d = jax.device_put(params, built_d.in_shardings[0])
        cache = jax.device_put(cache, built_d.in_shardings[1])
        dbatch = jax.device_put(dbatch, built_d.in_shardings[2])
        dl, cache = built_d.fn(params_d, cache, dbatch)
        assert dl.shape == (4, 1, vocab)
        assert bool(jnp.all(jnp.isfinite(dl)))
        results.append((arch, loss))
print("OK", results)
""",
        n_devices=8,
        timeout=900,
    )
    assert "OK" in out
