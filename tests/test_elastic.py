"""Elastic rescale: a checkpoint written on one topology restores onto a
different device count with the new mesh's shardings (reshard-on-load)."""

import pytest

from conftest import run_in_subprocess


@pytest.mark.slow
def test_checkpoint_restores_across_device_counts(tmp_path):
    ck = str(tmp_path)
    # phase 1: single device writes the checkpoint
    run_in_subprocess(
        f"""
import jax, jax.numpy as jnp
from repro.checkpoint import save_checkpoint
from repro.models.registry import get_model
from repro.optim import adamw_init
model = get_model("gemma2-27b", smoke=True)
params = model.init_params(jax.random.PRNGKey(0))
save_checkpoint({ck!r}, 5, {{"params": params, "opt": adamw_init(params)}},
                metadata={{"arch": "gemma2-27b"}})
print("SAVED")
""",
        n_devices=1,
    )
    # phase 2: 8-device mesh restores under sharded placement and trains
    out = run_in_subprocess(
        f"""
import jax, jax.numpy as jnp, numpy as np
from repro.checkpoint import load_checkpoint
from repro.launch.shapes import InputShape
from repro.launch.steps import build_train_step
from repro.models.registry import get_model
from repro.optim import adamw_init

assert len(jax.devices()) == 8
from repro.launch.mesh import make_auto_mesh
mesh = make_auto_mesh((2, 4), ("data", "model"))
with jax.set_mesh(mesh):
    model = get_model("gemma2-27b", smoke=True)
    like_p = model.param_shapes()
    like_o = jax.eval_shape(adamw_init, like_p)
    built = build_train_step(model, mesh, InputShape("t", "train", 32, 4))
    state, meta, step = load_checkpoint(
        {ck!r},
        {{"params": like_p, "opt": like_o}},
        shardings={{"params": built.in_shardings[0], "opt": built.in_shardings[1]}},
    )
    assert step == 5 and meta["arch"] == "gemma2-27b"
    # restored leaves actually carry the new mesh's shardings
    emb = state["params"]["embed"]
    assert len(emb.sharding.device_set) > 1, emb.sharding
    toks = jnp.ones((4, 32), jnp.int32)
    batch = jax.device_put({{"tokens": toks, "labels": toks}}, built.in_shardings[2])
    p2, o2, metrics = built.fn(state["params"], state["opt"], batch)
    assert np.isfinite(float(metrics["loss"]))
    print("OK", float(metrics["loss"]))
""",
        n_devices=8,
    )
    assert "OK" in out
