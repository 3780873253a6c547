"""Serving driver: continuous-batching slot scheduler for LM decode and
for epidemiology posterior queries.

LM mode (decoder-family archs; batched prefill + decode loop):

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
        --requests 8 --prompt-len 16 --gen 8

Epidemiology mode (the paper workload's outward face): batched posterior
forecast / counterfactual queries answered from cached fits — queries
sharing a compiled forecast shape are microbatched into ONE compiled call
(see repro.core.serving). On-demand fits run SMC-ABC waves by default;
`--backend npe` swaps in the amortized estimator (repro.core.npe), making
every fit a forward pass after one training run:

    PYTHONPATH=src python -m repro.launch.serve --epi \
        --queries queries.json --data-dir data/ --store store/ --days 21

Both modes implement the paper-inspired fixed-shape service pattern: a
static batch of slots, requests slotted in/out of it (continuous
batching), per-slot state written in place — the serving analogue of the
ABC engine's fixed-shape outfeed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.ioutils import atomic_write_text
from repro.launch.mesh import make_host_mesh
from repro.models.registry import get_model


# ----------------------------------------------------------------- LM mode
def _is_axes(x) -> bool:
    """Leaf predicate for cache_logical trees: a tuple of axis names."""
    return isinstance(x, tuple) and all(
        e is None or isinstance(e, str) for e in x
    )


def zero_slot(cache, logical, slot: int):
    """Zero one slot's lanes across every cache leaf (KV rows AND ssm/conv
    state). A freed slot's cache still holds the previous occupant's
    prefix; without this, the next request admitted into the slot attends
    over (or, for SSM state, integrates) stale context."""
    leaves, treedef = jax.tree.flatten(cache)
    axes = jax.tree.leaves(logical, is_leaf=_is_axes)
    assert len(leaves) == len(axes), (len(leaves), len(axes))
    out = []
    for arr, ax in zip(leaves, axes):
        b = ax.index("batch")
        out.append(arr.at[(slice(None),) * b + (slot,)].set(0))
    return jax.tree.unflatten(treedef, out)


def run_lm_server(model, prompts, gen: int, slots: int, cache_len: int):
    """Continuous-batching greedy decode; returns (outputs, steps).

    `outputs[i]` is the generated token list for `prompts[i]` (submission
    order), regardless of which slot served it or how many slot
    generations preceded it. Each slot advances at its OWN position — the
    decode step takes a [slots] pos vector, so a slot admitted mid-stream
    (or serving a shorter prompt) writes and attends its own cache prefix
    instead of the longest slot's. Admission zeroes the slot's cache
    lanes. Together these make batched outputs token-for-token identical
    to serving each request alone (pinned by tests/test_serve_slots.py).
    """
    logical = model.cache_logical()
    params = model.init_params(jax.random.PRNGKey(0))
    cache_shapes = model.init_cache_shape(slots, cache_len)
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache_shapes,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    decode = jax.jit(model.decode_step, donate_argnums=(1,))

    queue = list(range(len(prompts)))
    outputs = [None] * len(prompts)
    slot_req = [None] * slots  # request index occupying each slot
    slot_pos = np.zeros(slots, np.int64)
    slot_out = [[] for _ in range(slots)]
    steps = 0
    while queue or any(r is not None for r in slot_req):
        for s in range(slots):
            if slot_req[s] is None and queue:
                slot_req[s] = queue.pop(0)
                slot_pos[s] = 0
                slot_out[s] = []
                cache = zero_slot(cache, logical, s)
        toks = np.zeros((slots, 1), np.int32)
        for s, ri in enumerate(slot_req):
            if ri is None:
                continue
            p = int(slot_pos[s])
            if p < len(prompts[ri]):
                toks[s, 0] = prompts[ri][p]  # still consuming the prompt
            elif slot_out[s]:
                toks[s, 0] = slot_out[s][-1]
        # per-slot positions: each slot writes ITS next cache row
        pos = jnp.asarray(slot_pos, jnp.int32)
        logits, cache = decode(
            params, cache, {"tokens": jnp.asarray(toks), "pos": pos}
        )
        steps += 1
        nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
        for s, ri in enumerate(slot_req):
            if ri is None:
                continue
            slot_pos[s] += 1
            if slot_pos[s] >= len(prompts[ri]):
                slot_out[s].append(int(nxt[s]))
            if len(slot_out[s]) >= gen:
                outputs[ri] = slot_out[s]
                slot_req[s] = None
    return outputs, steps


def run_lm_cli(args):
    model = get_model(args.arch, smoke=args.smoke)
    if model.family == "encdec":
        raise SystemExit("serve.py LM mode drives decoder-family archs")
    mesh = make_host_mesh()
    vocab = model.cfg.vocab if hasattr(model.cfg, "vocab") else model.cfg.lm.vocab
    cache_len = args.prompt_len + args.gen

    with jax.set_mesh(mesh):
        rng = np.random.default_rng(0)
        prompts = [
            rng.integers(0, vocab, size=args.prompt_len).astype(np.int32).tolist()
            for _ in range(args.requests)
        ]
        t0 = time.time()
        outputs, steps = run_lm_server(
            model, prompts, args.gen, args.slots, cache_len
        )
        dt = time.time() - t0
        print(
            f"[serve] {len(outputs)} requests, {steps} decode steps, "
            f"{steps * args.slots / dt:.1f} tok/s (host mesh, CPU)"
        )
        for i, (req, out) in enumerate(zip(prompts, outputs)):
            if i >= 3:
                break
            print(f"  req{i}: prompt[:4]={req[:4]} -> gen={out}")
        return len(outputs)


# ---------------------------------------------------------------- epi mode
def _load_queries(path: str):
    from repro.core.serving import ForecastQuery

    with open(path) as f:
        raw = json.load(f)
    if isinstance(raw, dict):
        raw = raw["queries"]
    if not isinstance(raw, list) or not raw:
        raise SystemExit(f"--queries {path!r}: expected a non-empty list")
    return [ForecastQuery.from_json(q) for q in raw]


def run_epi_cli(args):
    from repro.core.serving import EpiServer, ServeConfig
    from repro.core.smc import SMCConfig

    if not args.queries:
        raise SystemExit("--epi requires --queries FILE.json")
    queries = _load_queries(args.queries)
    cfg = ServeConfig(
        slots=args.slots,
        forecast_particles=args.particles,
        fit=SMCConfig(
            n_particles=args.fit_particles,
            batch_size=args.fit_batch,
            n_rounds=args.fit_rounds,
            quantile=args.fit_quantile,
            num_days=args.days,
            backend=args.fit_backend,
        ),
        fit_seed=args.seed,
        data_dir=args.data_dir or None,
        store_dir=args.store or None,
        fit_backend=args.backend,
    )
    server = EpiServer(cfg)
    t0 = time.time()
    responses = server.answer(queries)
    stats = server.stats()
    stats["wall_time_s"] = time.time() - t0
    text = json.dumps(
        {"responses": responses, "stats": stats}, indent=1, allow_nan=False
    )
    if args.out:
        atomic_write_text(args.out, text)
        print(f"[serve] {len(responses)} responses saved to {args.out}",
              file=sys.stderr)
    else:
        print(text)
    print(
        f"[serve --epi] {len(responses)} queries, {stats['fits']} fits "
        f"({stats['warm_fits']} warm), {stats['npe_trains']} npe trains "
        f"({stats['npe_fine_tunes']} fine-tunes), "
        f"{stats['batched_calls']} batched "
        f"calls over {stats['compiled_shapes']} compiled shapes, "
        f"{stats['wall_time_s']:.2f}s",
        file=sys.stderr,
    )
    return len(responses)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="LM architecture to serve (LM mode; registry name)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode batch slots (LM) / query lanes per "
                         "compiled batch (--epi)")
    # epidemiology serving -------------------------------------------------
    ap.add_argument("--epi", action="store_true",
                    help="serve epidemiology posterior queries instead of "
                         "an LM: answer a batch of forecast/counterfactual "
                         "queries from cached SMC-ABC posteriors")
    ap.add_argument("--queries", default="",
                    help="JSON file: list of query objects (dataset, model, "
                         "horizon, schedule, quantiles, seed), or "
                         "{'queries': [...]}")
    ap.add_argument("--data-dir", default="",
                    help="directory of <name>.json dataset files (bundled "
                         "registry datasets resolve otherwise)")
    ap.add_argument("--store", default="",
                    help="posterior-store directory (persist fits across "
                         "invocations; the abc_serve daemon refreshes it)")
    ap.add_argument("--out", default="",
                    help="response JSON path (default: stdout)")
    ap.add_argument("--particles", type=int, default=128,
                    help="posterior particles per forecast")
    ap.add_argument("--days", type=int, default=21,
                    help="SMC fit window (days of observed data)")
    ap.add_argument("--fit-particles", type=int, default=128)
    ap.add_argument("--fit-batch", type=int, default=4096)
    ap.add_argument("--fit-rounds", type=int, default=3)
    ap.add_argument("--fit-quantile", type=float, default=0.5)
    ap.add_argument("--fit-backend", default="xla_fused",
                    choices=["xla", "xla_fused", "pallas"],
                    help="simulation backend of the SMC waves "
                         "(--backend smc only)")
    ap.add_argument("--backend", default="smc", choices=["smc", "npe"],
                    help="on-demand fit mechanism (--epi): SMC-ABC waves, "
                         "or an amortized NPE estimator (train once, "
                         "forward-pass per query; see core/npe.py)")
    ap.add_argument("--seed", type=int, default=0, help="fit seed (--epi)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.epi:
        if args.arch:
            ap.error("--arch has no effect with --epi")
        return run_epi_cli(args)
    if not args.arch:
        ap.error("--arch is required (LM mode); or pass --epi")
    return run_lm_cli(args)


if __name__ == "__main__":
    main()
