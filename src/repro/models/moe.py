"""Mixture-of-Experts layer with expert-parallel execution.

Experts are sharded over the ``model`` mesh axis. Activations are sharded
over the batch (``data``/``pod``) axes and replicated over ``model``, so each
model shard (a) computes the router identically, (b) gathers only the tokens
routed to *its* experts via a capacity-bounded dispatch table, (c) runs its
local experts, and (d) contributes its partial token outputs to a
``psum`` over ``model`` — the same collective a tensor-parallel dense MLP
needs, i.e. EP comes at no extra collective cost in this 2D mesh.

Two implementations:
  * ``moe_apply``        — shard_map EP path (production default).
  * ``moe_apply_einsum`` — one-hot dispatch-einsum reference (Mesh-TF style);
    kept as the naive baseline for the perf hillclimb and for correctness
    cross-checks in tests.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, RunConfig
from repro.models.common import ParamDef, act_fn

PyTree = Any


def moe_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    d, dff, e = cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.n_experts
    defs = {
        "router": ParamDef((d, e), ("embed", "experts")),
        "w_gate": ParamDef((e, d, dff), ("experts", "embed", "mlp")),
        "w_up": ParamDef((e, d, dff), ("experts", "embed", "mlp")),
        "w_down": ParamDef((e, dff, d), ("experts", "mlp", "embed")),
    }
    if cfg.shared_expert:
        defs.update({
            "sh_gate": ParamDef((d, cfg.d_ff), ("embed", "mlp")),
            "sh_up": ParamDef((d, cfg.d_ff), ("embed", "mlp")),
            "sh_down": ParamDef((cfg.d_ff, d), ("mlp", "embed")),
        })
    return defs


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts) + 1
    return max(8, -(-c // 8) * 8)  # round up to 8


def _route(x: jax.Array, router: jax.Array, cfg: ModelConfig):
    """Top-k routing. x: (T, d). Returns (idx (T,k), gate (T,k), aux_loss)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router.astype(jnp.float32))
    gates_all = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(gates_all, cfg.top_k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch-style) + router z-loss
    me = gates_all.mean(0)
    ce = jnp.zeros((cfg.n_experts,), jnp.float32).at[idx.reshape(-1)].add(
        1.0 / idx.size)
    aux = cfg.n_experts * jnp.sum(me * ce)
    aux = aux + 1e-4 * jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return idx, gate, aux


def _dispatch_tables(idx: jax.Array, n_experts: int, capacity: int):
    """Build (E, C) token-slot tables from (T, k) expert assignments.

    Returns token_id (E, C) int32 (-1 = empty), slot_of (T, k) int32
    (position within expert, >= capacity means dropped).
    """
    T, k = idx.shape
    flat = idx.reshape(-1)                                  # (T*k,)
    onehot = jax.nn.one_hot(flat, n_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1                    # pos within expert
    slot = jnp.take_along_axis(pos, flat[:, None], axis=1)[:, 0]
    tok = jnp.arange(T * k, dtype=jnp.int32) // k
    keep = slot < capacity
    token_id = jnp.full((n_experts, capacity), -1, jnp.int32)
    token_id = token_id.at[
        jnp.where(keep, flat, n_experts),                   # OOB row drops
        jnp.where(keep, slot, 0)].set(tok, mode="drop")
    return token_id, slot.reshape(T, k)


def _expert_ffn(xg: jax.Array, wg, wu, wd, act) -> jax.Array:
    """xg: (E_loc, C, d) -> (E_loc, C, d)."""
    wg, wu, wd = (w.astype(xg.dtype) for w in (wg, wu, wd))
    h = act(jnp.einsum("ecd,edf->ecf", xg, wg)) * jnp.einsum("ecd,edf->ecf", xg, wu)
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _moe_local(x: jax.Array, p: Dict[str, jax.Array], cfg: ModelConfig,
               act, e_lo: jax.Array, n_local: int) -> Tuple[jax.Array, jax.Array]:
    """Per-shard MoE: x (T, d) replicated router -> partial out for local experts.

    e_lo: first local expert id; n_local: experts owned by this shard.
    Output must be psum-med over the expert-sharding axis by the caller.
    """
    T, d = x.shape
    C = _capacity(T, cfg)
    idx, gate, aux = _route(x, p["router"], cfg)
    token_id, slot = _dispatch_tables(idx, cfg.n_experts, C)
    local_tok = jax.lax.dynamic_slice_in_dim(token_id, e_lo, n_local, 0)  # (E_loc, C)
    wg = jax.lax.dynamic_slice_in_dim(p["w_gate"], e_lo, n_local, 0)
    wu = jax.lax.dynamic_slice_in_dim(p["w_up"], e_lo, n_local, 0)
    wd = jax.lax.dynamic_slice_in_dim(p["w_down"], e_lo, n_local, 0)
    xg = jnp.where((local_tok >= 0)[..., None],
                   x[jnp.clip(local_tok, 0), :], 0.0)       # (E_loc, C, d)
    yg = _expert_ffn(xg.astype(x.dtype), wg, wu, wd, act)   # (E_loc, C, d)
    # combine back: for each (t, k) whose expert is local and slot kept
    out = jnp.zeros((T, d), jnp.float32)
    k = cfg.top_k
    e_flat = idx.reshape(-1)
    s_flat = slot.reshape(-1)
    t_flat = jnp.arange(T * k) // k
    g_flat = gate.reshape(-1)
    is_local = (e_flat >= e_lo) & (e_flat < e_lo + n_local) & (s_flat < C)
    rows = jnp.where(is_local, e_flat - e_lo, 0)
    vals = yg[rows, jnp.where(is_local, s_flat, 0), :]
    vals = jnp.where(is_local[:, None], vals.astype(jnp.float32) * g_flat[:, None], 0.0)
    out = out.at[t_flat].add(vals)
    # aux loss is identical on every shard; divide so psum restores it
    return out.astype(x.dtype), aux


def moe_apply(x: jax.Array, p: Dict[str, jax.Array], cfg: ModelConfig,
              run: RunConfig, mesh=None, batch_axes: Tuple[str, ...] = ("data",)
              ) -> Tuple[jax.Array, jax.Array]:
    """MoE layer. x: (B, S, d) sharded over batch_axes, replicated over model.

    Returns (y (B,S,d), aux_loss scalar).
    """
    B, S, d = x.shape
    act = act_fn(cfg.act)

    if mesh is None or "model" not in getattr(mesh, "axis_names", ()) or \
            mesh.shape.get("model", 1) == 1:
        y2, aux = _moe_local(x.reshape(B * S, d), p, cfg, act, jnp.int32(0),
                             cfg.n_experts)
        y = y2.reshape(B, S, d)
    else:
        tp = mesh.shape["model"]
        n_local = cfg.n_experts // tp
        assert n_local * tp == cfg.n_experts, \
            f"{cfg.n_experts} experts not divisible by model={tp}"
        pspec_x = P(batch_axes, None, None)
        pspec_w3 = P("model", None, None)
        pspec_r = P(None, None)

        def shard_fn(xs, router, wg, wu, wd):
            e_lo = jax.lax.axis_index("model") * n_local
            Bl, Sl, _ = xs.shape
            pl = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
            y2, aux = _moe_local(xs.reshape(Bl * Sl, d), pl, cfg, act, e_lo,
                                 n_local)
            y2 = jax.lax.psum(y2, "model")
            # aux is identical across `model` shards (same tokens, same
            # router); psum/tp keeps it differentiable (pmin has no VJP).
            # Across data/pod shards tokens differ -> average (the standard
            # per-DP-shard aux-loss semantics).
            aux = jax.lax.psum(aux, "model") / tp
            data_axes = tuple(a for a in mesh.axis_names if a != "model")
            if data_axes:
                aux = jax.lax.pmean(aux, data_axes)
            return y2.reshape(Bl, Sl, d), aux

        y, aux = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(pspec_x, pspec_r, pspec_w3, pspec_w3, pspec_w3),
            out_specs=(pspec_x, P()),
            check_vma=False,
        )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if cfg.shared_expert:
        h = act(jnp.einsum("bsd,df->bsf", x, p["sh_gate"].astype(x.dtype))) * \
            jnp.einsum("bsd,df->bsf", x, p["sh_up"].astype(x.dtype))
        y = y + jnp.einsum("bsf,fd->bsd", h, p["sh_down"].astype(x.dtype))
    return y, aux


def moe_apply_einsum(x: jax.Array, p: Dict[str, jax.Array], cfg: ModelConfig
                     ) -> Tuple[jax.Array, jax.Array]:
    """One-hot dispatch-einsum reference (naive baseline; O(T·E·C·d) dispatch)."""
    B, S, d = x.shape
    T = B * S
    act = act_fn(cfg.act)
    xf = x.reshape(T, d)
    C = _capacity(T, cfg)
    idx, gate, aux = _route(xf, p["router"], cfg)
    token_id, slot = _dispatch_tables(idx, cfg.n_experts, C)
    # dispatch one-hot (T, E, C); gates apply at COMBINE only (the expert
    # nonlinearity must see the raw token)
    k = cfg.top_k
    t_flat = jnp.arange(T * k) // k
    keep = (slot.reshape(-1) < C)
    disp = jnp.zeros((T, cfg.n_experts, C), x.dtype)
    disp = disp.at[t_flat, idx.reshape(-1),
                   jnp.clip(slot.reshape(-1), 0, C - 1)].add(
        jnp.where(keep, 1.0, 0.0).astype(x.dtype))
    comb = jnp.zeros((T, cfg.n_experts, C), x.dtype)
    comb = comb.at[t_flat, idx.reshape(-1),
                   jnp.clip(slot.reshape(-1), 0, C - 1)].add(
        jnp.where(keep, gate.reshape(-1), 0.0).astype(x.dtype))
    xg = jnp.einsum("tec,td->ecd", disp, xf)
    yg = _expert_ffn(xg, p["w_gate"], p["w_up"], p["w_down"], act)
    y = jnp.einsum("tec,ecd->td", comb, yg).reshape(B, S, d)
    if cfg.shared_expert:
        h = act(jnp.einsum("bsd,df->bsf", x, p["sh_gate"].astype(x.dtype))) * \
            jnp.einsum("bsd,df->bsf", x, p["sh_up"].astype(x.dtype))
        y = y + jnp.einsum("bsf,fd->bsd", h, p["sh_down"].astype(x.dtype))
    return y, aux
