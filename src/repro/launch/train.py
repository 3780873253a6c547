"""LM training driver.

    PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --smoke \
        --steps 20 --batch 8 --seq 128 --ckpt-dir /tmp/ck --ckpt-every 10

Production pod usage is the same entry point with the full arch name and
`--mesh single|multi`; on this CPU container use --smoke (reduced config on
the host mesh). Features: ZeRO-1-sharded AdamW, async checkpointing with
resume, optional int8 error-feedback gradient compression, deterministic
(step, shard)-addressed data — so restart/elastic-rescale does not change
the sample stream.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer
from repro.data import SyntheticTokenDataset
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.shapes import InputShape
from repro.launch.steps import build_train_step
from repro.models.registry import get_model
from repro.optim import AdamWConfig, adamw_init


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config, host mesh")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default="host", choices=["host", "single", "multi"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    model = get_model(args.arch, smoke=args.smoke)
    if args.mesh == "host":
        mesh = make_host_mesh()
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    shape = InputShape("cli", "train", args.seq, args.batch)
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps, warmup_steps=min(20, args.steps // 5 + 1))

    vocab = model.cfg.vocab if hasattr(model.cfg, "vocab") else model.cfg.lm.vocab
    ds = SyntheticTokenDataset(vocab=vocab, seq_len=args.seq, seed=0)

    with jax.set_mesh(mesh):
        built = build_train_step(model, mesh, shape, opt_cfg=opt_cfg, donate=True)
        params = model.init_params(jax.random.PRNGKey(0))
        opt_state = adamw_init(params)

        start_step = 0
        ck = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
        if ck and args.resume and ck.steps():
            state, meta, start_step = ck.restore({"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            print(f"[train] resumed from step {start_step}")

        if model.family not in ("decoder", "ssm", "hybrid"):
            raise SystemExit(
                "train.py drives token-LM training; use the benchmarks for "
                f"family={model.family}"
            )

        t0 = time.time()
        tokens_seen = 0
        for step in range(start_step, args.steps):
            raw = ds.batch(step, args.batch)
            batch = {k: jnp.asarray(v) for k, v in raw.items()}
            params, opt_state, metrics = built.fn(params, opt_state, batch)
            tokens_seen += args.batch * args.seq
            if step % args.log_every == 0 or step == args.steps - 1:
                m = jax.tree.map(float, metrics)
                print(
                    f"[train] step {step:5d} loss={m['loss']:.4f} "
                    f"gnorm={m['grad_norm']:.3f} lr={m['lr']:.2e} "
                    f"tok/s={tokens_seen / (time.time() - t0):.0f}",
                    flush=True,
                )
            if ck and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
                ck.save_async(step + 1, {"params": params, "opt": opt_state},
                              metadata={"arch": args.arch})
        if ck:
            ck.wait()
            ck.save(args.steps, {"params": params, "opt": opt_state},
                    metadata={"arch": args.arch})
        print(f"[train] done in {time.time() - t0:.1f}s")
        return float(jax.tree.map(float, metrics)["loss"])


if __name__ == "__main__":
    main()
