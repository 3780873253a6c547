"""Mixture-of-Experts FFN: fine-grained routed experts + optional shared experts.

Covers deepseek-moe-16b (2 shared + 64 routed, top-6) and qwen3-moe-30b-a3b
(128 routed, top-8). Dispatch is capacity-based scatter/gather (no [N,E,C]
one-hot tensor): tokens are placed into per-expert buffers [E, C, d] whose
expert axis shards over the "model" mesh axis (expert parallelism) — the SPMD
partitioner emits the all-to-all traffic that the roofline collective term
measures. Router math in f32; a switch-style load-balancing aux loss is
returned for training.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models import common as cm


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int  # per-expert FFN width (fine-grained)
    n_shared: int = 0  # shared (always-on) experts of the same width
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    #: steer GSPMD: pin tokens to the data axes and expert buffers to the
    #: model axis around the dispatch scatter/gather (EXPERIMENTS.md §Perf —
    #: without these the partitioner replicates the dispatch; toggle via env
    #: REPRO_MOE_CONSTRAIN=0 to reproduce the baseline)
    shard_constraints: bool = os.environ.get("REPRO_MOE_CONSTRAIN", "1") == "1"


def _ambient_mesh():
    """The mesh installed by `jax.set_mesh`, or None when no mesh (or an
    empty one) is in scope, so constraints are skipped (single-process
    smoke tests)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return None
    return mesh


def _constrain(x, *logical):
    """Best-effort sharding constraint using the ambient (abstract) mesh.

    logical entries: 'tokens' -> data axes, 'experts' -> model axis, None.
    Skipped entirely when no mesh is set (smoke tests) or dims don't divide.
    """
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)
    dp = tuple(a for a in ("pod", "data") if a in names)

    def axes_for(l):
        if l == "tokens" and dp:
            return dp
        if l == "experts" and "model" in names:
            return "model"
        return None

    parts = []
    for dim, l in zip(x.shape, logical):
        a = axes_for(l)
        if a is not None:
            sz = 1
            for ax in ((a,) if isinstance(a, str) else a):
                sz *= mesh.shape[ax]
            if dim % sz != 0:
                a = None
        parts.append(a)
    return jax.lax.with_sharding_constraint(x, P(*parts))


def init_moe(key, d_model: int, cfg: MoEConfig):
    ks = cm.keygen(key)
    e, f = cfg.n_experts, cfg.d_expert
    p = {
        "router": cm.ninit(next(ks), (d_model, e), d_model, jnp.float32),
        "wg": cm.ninit(next(ks), (e, d_model, f), d_model),
        "wu": cm.ninit(next(ks), (e, d_model, f), d_model),
        "wd": cm.ninit(next(ks), (e, f, d_model), f),
    }
    if cfg.n_shared:
        fs = f * cfg.n_shared
        p["shared_wg"] = cm.ninit(next(ks), (d_model, fs), d_model)
        p["shared_wu"] = cm.ninit(next(ks), (d_model, fs), d_model)
        p["shared_wd"] = cm.ninit(next(ks), (fs, d_model), fs)
    return p


def moe_logical(cfg: MoEConfig):
    spec = {
        "router": ("embed", "experts"),
        "wg": ("experts", "embed", "expert_ffn"),
        "wu": ("experts", "embed", "expert_ffn"),
        "wd": ("experts", "expert_ffn", "embed"),
    }
    if cfg.n_shared:
        spec["shared_wg"] = ("embed", "ffn")
        spec["shared_wu"] = ("embed", "ffn")
        spec["shared_wd"] = ("ffn", "embed")
    return spec


def capacity(n_tokens: int, cfg: MoEConfig) -> int:
    cf = float(os.environ.get("REPRO_MOE_CF", cfg.capacity_factor))
    c = int(np.ceil(n_tokens * cfg.top_k * cf / cfg.n_experts))
    return max(8, int(np.ceil(c / 8) * 8))


def _dp_group_count(n_tokens: int) -> int:
    """Number of data shards (dispatch groups) from the ambient mesh."""
    mesh = _ambient_mesh()
    if mesh is None:
        return 1
    g = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            g *= mesh.shape[a]
    return g if g > 1 and n_tokens % g == 0 else 1


def moe_ffn(
    x: jax.Array, p: dict, cfg: MoEConfig, act: str = "silu"
) -> Tuple[jax.Array, jax.Array]:
    """Grouped expert-parallel dispatch (EXPERIMENTS.md §Perf, qwen3 cell).

    Tokens are dispatched into PER-DATA-SHARD capacity buffers
    [G, E, C_local, d] (scatter stays shard-local), then a single transpose
    G <-> E moves tokens to their expert shards — the canonical EP
    all-to-all. The naive global-buffer formulation (moe_ffn_global) forced
    GSPMD to ALL-REDUCE the full [E, C, d] buffer across the data axis every
    layer (~3.3 TB/device wire on qwen3 train_4k); grouped dispatch replaces
    that with the all-to-all, which is smaller by ~G x.

    x: [B, S, d] -> (y [B, S, d], aux_loss scalar f32).
    """
    if os.environ.get("REPRO_MOE_GROUPED", "1") != "1":
        return moe_ffn_global(x, p, cfg, act)
    b, s, d = x.shape
    n = b * s
    e, k = cfg.n_experts, cfg.top_k
    g = _dp_group_count(n)
    m = n // g  # tokens per group
    c = capacity(m, cfg)  # LOCAL capacity
    cons = _constrain if cfg.shard_constraints else (lambda t, *a: t)
    xg = cons(x.reshape(g, m, d), "tokens", None, None)

    logits = (xg.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # [G,M,E]
    # keep logits replicated over "model": top_k needs every expert column,
    # so an E-sharded layout forces a [G,M,E] f32 all-gather per layer; with
    # this constraint GSPMD gathers the 1 MB router param instead.
    logits = cons(logits, "tokens", None, None)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, k)  # [G, M, k]
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    f_e = jnp.zeros(e, jnp.float32).at[top_ids.reshape(-1)].add(1.0) / (n * k)
    p_e = probs.mean(axis=(0, 1))
    aux = cfg.router_aux_weight * e * jnp.sum(f_e * p_e)

    # ---- per-group dispatch (shard-local scatter) ----
    ids_g = top_ids.reshape(g, m * k)  # [G, M*k]
    w_g = top_w.reshape(g, m * k)
    oh = jax.nn.one_hot(ids_g, e, dtype=jnp.int32)  # [G, M*k, E]
    pos = jnp.take_along_axis(
        jnp.cumsum(oh, axis=1) - 1, ids_g[..., None], axis=2
    )[..., 0]  # [G, M*k]
    keep = pos < c
    slot = jnp.clip(ids_g * c + pos, 0, e * c - 1)
    tok_idx = jnp.repeat(jnp.arange(m), k)  # [M*k]
    src = jnp.where(keep[..., None], xg[:, tok_idx, :], 0).astype(x.dtype)
    src = cons(src, "tokens", None, None)
    buf_g = jax.vmap(lambda sl, u: jnp.zeros((e * c, d), x.dtype).at[sl].add(u))(
        slot, src
    )  # [G, E*C, d], G on data axes
    buf_g = cons(buf_g.reshape(g, e, c, d), "tokens", "experts", None, None)

    # ---- the EP all-to-all: groups -> expert shards ----
    buf_e = cons(
        jnp.swapaxes(buf_g, 0, 1).reshape(e, g * c, d), "experts", None, None
    )
    h = jnp.einsum("ecd,edf->ecf", buf_e, p["wg"])
    hu = jnp.einsum("ecd,edf->ecf", buf_e, p["wu"])
    if act == "silu":
        h = jax.nn.silu(h.astype(jnp.float32)).astype(x.dtype)
    else:
        h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(x.dtype)
    out_e = jnp.einsum("ecf,efd->ecd", h * hu, p["wd"])  # [E, G*C, d]
    out_e = cons(out_e, "experts", None, None)

    # ---- all-to-all back + per-group combine ----
    out_g = jnp.swapaxes(out_e.reshape(e, g, c, d), 0, 1)  # [G, E, C, d]
    out_g = cons(out_g, "tokens", "experts", None, None).reshape(g, e * c, d)
    gathered = jnp.take_along_axis(out_g, slot[..., None], axis=1)  # [G, M*k, d]
    gathered = jnp.where(keep[..., None], gathered, 0)
    # combine in bf16: f32 here doubled every dispatch collective AND flipped
    # the backward buffers to f32 (measured 2x wire on qwen3; §Perf iter 2)
    y = (
        (gathered * w_g[..., None].astype(x.dtype))
        .reshape(g, m, k, d)
        .sum(axis=2)
        .reshape(b, s, d)
    )

    if cfg.n_shared:
        y = y + cm.gated_mlp(x, p["shared_wg"], p["shared_wu"], p["shared_wd"], act)
    return y, aux


def moe_ffn_global(
    x: jax.Array, p: dict, cfg: MoEConfig, act: str = "silu"
) -> Tuple[jax.Array, jax.Array]:
    """Baseline global-capacity dispatch (kept for the §Perf record).

    x: [B, S, d] -> (y [B, S, d], aux_loss scalar f32)."""
    b, s, d = x.shape
    n = b * s
    e, k = cfg.n_experts, cfg.top_k
    c = capacity(n, cfg)
    xf = x.reshape(n, d)
    cons = _constrain if cfg.shard_constraints else (lambda t, *a: t)
    xf = cons(xf, "tokens", None)

    logits = (xf.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, k)  # [N, k]
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # switch-style load-balance aux: E * sum_e f_e * p_e
    f_e = jnp.zeros(e, jnp.float32).at[top_ids.reshape(-1)].add(1.0) / (n * k)
    p_e = probs.mean(axis=0)
    aux = cfg.router_aux_weight * e * jnp.sum(f_e * p_e)

    # ---- dispatch: position-in-expert via cumsum, scatter into [E*C, d] ----
    flat_ids = top_ids.reshape(-1)  # [N*k]
    flat_w = top_w.reshape(-1)
    tok_idx = jnp.repeat(jnp.arange(n), k)
    oh = jax.nn.one_hot(flat_ids, e, dtype=jnp.int32)  # [N*k, E]
    pos = jnp.take_along_axis(
        jnp.cumsum(oh, axis=0) - 1, flat_ids[:, None], axis=1
    )[:, 0]
    keep = pos < c
    slot = jnp.clip(flat_ids * c + pos, 0, e * c - 1)
    src = jnp.where(keep[:, None], xf[tok_idx], 0).astype(x.dtype)
    src = cons(src, "tokens", None)
    buf = jnp.zeros((e * c, d), x.dtype).at[slot].add(src).reshape(e, c, d)
    buf = cons(buf, "experts", None, None)

    # ---- expert FFN (einsum over the expert-sharded buffers) ----
    h = jnp.einsum("ecd,edf->ecf", buf, p["wg"])
    hu = jnp.einsum("ecd,edf->ecf", buf, p["wu"])
    if act == "silu":
        h = jax.nn.silu(h.astype(jnp.float32)).astype(x.dtype)
    else:
        h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(x.dtype)
    h = cons(h, "experts", None, None)
    out_buf = jnp.einsum("ecf,efd->ecd", h * hu, p["wd"])
    out_buf = cons(out_buf, "experts", None, None).reshape(e * c, d)

    # ---- combine: gather back, weight, sum over the k slots ----
    gathered = jnp.where(keep[:, None], out_buf[slot], 0)
    gathered = cons(gathered, "tokens", None)
    y = (
        (gathered.astype(jnp.float32) * flat_w[:, None])
        .reshape(n, k, d)
        .sum(axis=1)
        .astype(x.dtype)
        .reshape(b, s, d)
    )

    if cfg.n_shared:
        y = y + cm.gated_mlp(x, p["shared_wg"], p["shared_wu"], p["shared_wd"], act)
    return y, aux
