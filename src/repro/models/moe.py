"""Mixture-of-Experts layer with sort-based (UPE) token dispatch.

Token→expert dispatch is a set-partitioning problem: partition the (token,
expert) assignment pairs by expert id — one multi-way UPE pass. Rank within
each expert bucket (an exclusive prefix sum, the same adder network) gives
the capacity slot; overflowing tokens are dropped (capacity_factor). This is
the contention-free, atomic-free dispatch the paper's primitives buy us in
the MoE context (DESIGN.md §4) — MegaBlocks-style, no [T,E,C] one-hot tensor.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.set_partition import prefix_sum
from repro.dist.hints import _current_mesh, mesh_info, shard_hint

from .common import Params, dense_init


def moe_init(key, d_model: int, d_ff: int, n_experts: int,
             dtype=jnp.float32) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    import math
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff)
    return {
        "router": dense_init(k1, d_model, n_experts, jnp.float32),
        "w_gate": (jax.random.normal(k2, (n_experts, d_model, d_ff),
                                     jnp.float32) * s_in).astype(dtype),
        "w_in": (jax.random.normal(k3, (n_experts, d_model, d_ff),
                                   jnp.float32) * s_in).astype(dtype),
        "w_out": (jax.random.normal(k4, (n_experts, d_ff, d_model),
                                    jnp.float32) * s_out).astype(dtype),
    }


def moe_apply(p: Params, x: jnp.ndarray, *, top_k: int,
              capacity_factor: float = 1.25,
              act=jax.nn.silu) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x [T, d] → (y [T, d], aux_loss scalar).

    Sort-based dispatch: one partition by expert id + prefix-sum ranks.
    """
    t, d = x.shape
    e = p["w_in"].shape[0]
    cap = int(capacity_factor * top_k * t / e + 0.5)
    cap = max(cap, 1)

    logits = (x.astype(jnp.float32) @ p["router"])  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)  # [T, k]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)  # renormalize

    # ---- UPE dispatch: partition (token, slot) pairs by expert ----------
    flat_e = top_e.reshape(-1)  # [T*k] expert ids
    flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), top_k)  # token ids
    flat_w = top_p.reshape(-1)
    onehot = (flat_e[:, None] == jnp.arange(e)[None, :]).astype(jnp.int32)
    within = prefix_sum(onehot, axis=0, exclusive=True)  # rank inside bucket
    rank = jnp.sum(onehot * within, axis=1)  # [T*k]
    keep = rank < cap
    slot = jnp.where(keep, flat_e * cap + rank, e * cap)  # OOB → dropped

    # expert axis shards over 'model' when experts cover it (granite 32/16);
    # otherwise d_ff is TP'd within each expert (grok 8 experts × 16-way ff)
    _, model_size = mesh_info()
    expert_parallel = e % max(model_size, 1) == 0 and e >= model_size
    e_ax = "model" if expert_parallel else None
    f_ax = None if expert_parallel else "model"

    # Scatter INDICES, gather data: a scatter of the [E·C, d] activations
    # forces GSPMD into a replicated [10.5M, d] update (observed on the
    # dry-run); scattering the int32 slot→token map is 1024× smaller, and
    # the subsequent gather shards cleanly.
    slot_token = jnp.full((e * cap,), t, jnp.int32)
    slot_token = slot_token.at[slot].set(flat_t, mode="drop")
    valid_slot = slot_token < t
    xe_flat = jnp.take(x, jnp.minimum(slot_token, t - 1), axis=0)
    xe_flat = jnp.where(valid_slot[:, None], xe_flat, 0)
    xe = shard_hint(xe_flat.reshape(e, cap, d), e_ax, "dp", None)

    # ---- expert GEMMs (grouped) -----------------------------------------
    h = act(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) * jnp.einsum(
        "ecd,edf->ecf", xe, p["w_in"])
    h = shard_hint(h, e_ax, "dp", f_ax)
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_out"])  # [E, C, d]
    ye = shard_hint(ye, e_ax, "dp", None)

    # ---- combine: gather each kept slot back, weighted -------------------
    y_slots = ye.reshape(e * cap, d)
    gathered = jnp.where(keep[:, None],
                         jnp.take(y_slots, jnp.minimum(slot, e * cap - 1),
                                  axis=0), 0.0)
    gathered = shard_hint(gathered, "dp", None)
    y = jax.ops.segment_sum(gathered * flat_w[:, None].astype(gathered.dtype),
                            flat_t, num_segments=t)
    y = shard_hint(y, "dp", None)

    # load-balancing aux loss (Switch): E * sum_e f_e * P_e
    f = jnp.mean((onehot * keep[:, None]).astype(jnp.float32), axis=0) * (
        t * top_k / jnp.maximum(t, 1))
    pe = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f * pe) / top_k
    return y.astype(x.dtype), aux


def moe_apply_local(p: Params, x: jnp.ndarray, *, top_k: int,
                    capacity_factor: float = 1.25,
                    act=jax.nn.silu) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Shard-local dispatch: per-data-shard capacity groups (GShard-style).

    Tokens reshape to [n_dp_shards, T_local, d] and ranks/slots are computed
    *within* each shard (vmap), so the dispatch gather/scatter never crosses
    a data shard — GSPMD would otherwise lower the global-rank gather to an
    all-reduce of the whole [E·C, d] buffer (grok-1: 12.4 TB/step/device,
    81% of all collective traffic; granite: 18 GB/layer — §Perf iters 1&4).
    Within-expert TP is preserved: the vmapped expert einsums still contract
    against model-sharded d_ff. Per-shard capacity = cap/n_shards (local
    load-balance groups, as in GShard/Switch).
    """
    mesh = _current_mesh()
    dp, model_size = mesh_info()
    n = 1
    for a in dp:
        n *= dict(mesh.shape)[a] if mesh is not None else 1
    t, d = x.shape
    if mesh is None or n <= 1 or t % n:
        return moe_apply(p, x, top_k=top_k, capacity_factor=capacity_factor,
                         act=act)
    e = p["w_in"].shape[0]
    tl = t // n  # tokens per shard
    cap = max(int(capacity_factor * top_k * tl / e + 0.5), 1)

    def hint(z, *axes):  # every step pinned — GSPMD must not replicate
        return shard_hint(z, *axes)

    xs = hint(x.reshape(n, tl, d), "dp", None, None)
    logits = xs.astype(jnp.float32) @ p["router"]  # [n, tl, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)  # [n, tl, k]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    flat_e = hint(top_e.reshape(n, tl * top_k), "dp", None)
    onehot = (flat_e[..., None] == jnp.arange(e)[None, None, :]
              ).astype(jnp.int32)  # [n, tl*k, E]
    onehot = hint(onehot, "dp", None, None)
    within = prefix_sum(onehot, axis=1, exclusive=True)
    rank = jnp.sum(onehot * within, axis=-1)  # [n, tl*k]
    keep = rank < cap
    slot = jnp.where(keep, flat_e * cap + rank, e * cap)  # local slot ids

    # scatter INDICES (token position within shard), then gather data —
    # both shard-local thanks to the leading n axis
    rows = jnp.arange(n, dtype=jnp.int32)[:, None]
    tok = jnp.broadcast_to(
        jnp.repeat(jnp.arange(tl, dtype=jnp.int32), top_k)[None],
        (n, tl * top_k))
    st = jnp.full((n, e * cap), tl, jnp.int32)
    st = st.at[rows, slot].set(tok, mode="drop")
    st = hint(st, "dp", None)
    valid = st < tl
    xe = jnp.take_along_axis(xs, jnp.minimum(st, tl - 1)[..., None], axis=1)
    xe = jnp.where(valid[..., None], xe, 0)
    xe = hint(xe.reshape(n, e, cap, d), "dp", None, None, None)

    # grouped expert GEMMs; d_ff stays model-sharded (within-expert TP)
    f_ax = None if model_size <= 1 else "model"
    h = act(jnp.einsum("necd,edf->necf", xe, p["w_gate"])) * jnp.einsum(
        "necd,edf->necf", xe, p["w_in"])
    h = hint(h, "dp", None, None, f_ax)
    ye = jnp.einsum("necf,efd->necd", h, p["w_out"])
    ye = hint(ye, "dp", None, None, None)

    y_slots = ye.reshape(n, e * cap, d)
    gathered = jnp.take_along_axis(
        y_slots, jnp.minimum(slot, e * cap - 1)[..., None], axis=1)
    gathered = jnp.where(keep[..., None], gathered, 0)  # [n, tl*k, d]
    # combine: slots are token-major → reshape + weighted sum over k
    w = top_p.reshape(n, tl, top_k).astype(gathered.dtype)
    y = jnp.sum(gathered.reshape(n, tl, top_k, d) * w[..., None], axis=2)
    y = hint(y, "dp", None, None)

    f = jnp.mean((onehot * keep[..., None]).astype(jnp.float32),
                 axis=(0, 1)) * top_k
    pe = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(f * pe) / top_k
    return y.reshape(t, d).astype(x.dtype), aux
