"""Inputs made on the device from ``--seed``: the graph, the feature table
and the model's weights, each in one jitted call.

The graph is symmetric, as the published Reddit and ogbn-products graphs
store both directions of every undirected edge: ``n_edges / 2`` pairs
``(a, b)`` are drawn and each is stored as ``a <- b`` and ``b <- a``. Both
endpoints follow one power law over node ranks, ``P(rank r) ~ r^-alpha``,
drawn by inverse CDF of the continuous law on ``[1, n + 1)``, and ranks
map to node ids through a seeded permutation. Self loops and repeated
pairs are kept, as a multigraph.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

SENTINEL = 0x7FFFFFFF


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (both 32-bit halves count)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def stream_key(seed: int, name: str) -> jax.Array:
    """An independent key per named input stream (graph, features, ...)."""
    return jax.random.fold_in(seed_key(seed),
                              sum(ord(c) << (8 * (i % 3))
                                  for i, c in enumerate(name)))


@partial(jax.jit, static_argnames=("n_nodes", "n_edges", "capacity",
                                   "alpha"))
def edge_arrays(key, *, n_nodes: int, n_edges: int, capacity: int,
                alpha: float):
    """``(dst, src)`` int32 arrays of ``capacity`` slots: the first
    ``n_edges`` hold the graph, the tail is SENTINEL."""
    if n_edges % 2 or n_edges > capacity:
        raise ValueError(f"n_edges {n_edges} must be even and <= capacity "
                         f"{capacity}")
    k_perm, k_a, k_b = jax.random.split(key, 3)
    perm = jax.random.permutation(k_perm, n_nodes).astype(jnp.int32)
    half = n_edges // 2
    a = 1.0 - alpha
    top = float(n_nodes + 1) ** a

    def endpoint(k):
        u = jax.random.uniform(k, (half,), jnp.float32)
        x = (1.0 + u * (top - 1.0)) ** (1.0 / a)
        rank = jnp.clip(jnp.floor(x).astype(jnp.int32) - 1, 0, n_nodes - 1)
        return jnp.take(perm, rank)

    u_end, v_end = endpoint(k_a), endpoint(k_b)
    pad = jnp.full((capacity - n_edges,), SENTINEL, jnp.int32)
    dst = jnp.concatenate([u_end, v_end, pad])
    src = jnp.concatenate([v_end, u_end, pad])
    return dst, src


def graph_arrays(seed: int, cfg: dict):
    """The configuration's graph for ``seed``, as device arrays."""
    g = cfg["graph"]
    return edge_arrays(stream_key(seed, "graph"), n_nodes=g["n_nodes"],
                       n_edges=g["n_edges"], capacity=g["capacity"],
                       alpha=g["assumed"]["alpha"])


@partial(jax.jit, static_argnames=("n_nodes", "d_feat", "dims", "n_classes"))
def _model_inputs(kf, kw, *, n_nodes, d_feat, dims, n_classes):
    feats = jax.random.normal(kf, (n_nodes, d_feat), jnp.float32)
    layers = []
    for d_in, d_out in dims:
        kw, k1, k2, k3 = jax.random.split(kw, 4)
        scale = 1.0 / jnp.sqrt(jnp.float32(d_in))
        layers.append({
            "w_self": jax.random.normal(k1, (d_in, d_out)) * scale,
            "w_nb": jax.random.normal(k2, (d_in, d_out)) * scale,
            "b": jax.random.normal(k3, (d_out,)) * 0.1,
        })
    d_last = dims[-1][1]
    head = (jax.random.normal(kw, (d_last, n_classes))
            / jnp.sqrt(jnp.float32(d_last)))
    return feats, {"layers": layers, "head": head}


def model_inputs(seed: int, cfg: dict):
    """(feature table [n_nodes, d_feat] f32, GraphSAGE weights) for
    ``seed``; the weights are laid out as ``{"layers": [{"w_self",
    "w_nb", "b"}], "head"}``."""
    g, m = cfg["graph"], cfg["model"]
    dims, d = [], g["d_feat"]
    for _ in range(m["n_layers"]):
        dims.append((d, m["d_hidden"]))
        d = m["d_hidden"]
    return _model_inputs(stream_key(seed, "features"),
                         stream_key(seed, "weights"), n_nodes=g["n_nodes"],
                         d_feat=g["d_feat"], dims=tuple(dims),
                         n_classes=g["n_classes"])
