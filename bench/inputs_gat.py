"""The GAT configuration's feature table and weights, made on the device
from ``--seed`` in one jitted call. The features are drawn as
``graphgen.model_inputs`` draws them, from the same stream."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.graphgen import stream_key


def layer_dims(cfg: dict) -> tuple[tuple[int, int, int, int], ...]:
    """(d_in, heads, width, d_out) of each layer: hidden layers concatenate
    ``heads`` heads of ``d_head``; the last has ``out_heads`` heads of the
    class count, which it averages."""
    g, m = cfg["graph"], cfg["model"]
    dims, d = [], g["d_feat"]
    for i in range(m["n_layers"]):
        if i < m["n_layers"] - 1:
            heads, width = m["heads"], m["d_head"]
            d_out = heads * width
        else:
            heads, width = m["out_heads"], g["n_classes"]
            d_out = width
        dims.append((d, heads, width, d_out))
        d = d_out
    return tuple(dims)


@partial(jax.jit, static_argnames=("n_nodes", "d_feat", "dims"))
def _inputs(kf, kw, *, n_nodes, d_feat, dims):
    feats = jax.random.normal(kf, (n_nodes, d_feat), jnp.float32)
    layers = []
    for d_in, heads, width, d_out in dims:
        kw, k1, k2, k3, k4, k5, k6 = jax.random.split(kw, 7)
        att = (6.0 / (heads + width)) ** 0.5
        scale = 1.0 / jnp.sqrt(jnp.float32(d_in))
        layers.append({
            "w": jax.random.normal(k1, (d_in, heads * width)) * scale,
            "a_src": jax.random.uniform(k2, (heads, width), minval=-att,
                                        maxval=att),
            "a_dst": jax.random.uniform(k3, (heads, width), minval=-att,
                                        maxval=att),
            "b": jax.random.normal(k4, (d_out,)) * 0.1,
            "w_skip": jax.random.normal(k5, (d_in, d_out)) * scale,
            "b_skip": jax.random.normal(k6, (d_out,)) * 0.1,
        })
    return feats, {"layers": layers}


def gat_inputs(seed: int, cfg: dict):
    """(feature table [n_nodes, d_feat] f32, GAT weights) for ``seed``,
    laid out as ``models.gnn`` lays out a GAT with skips: ``{"layers":
    [{"w", "a_src", "a_dst", "b", "w_skip", "b_skip"}]}``."""
    g = cfg["graph"]
    return _inputs(stream_key(seed, "features"), stream_key(seed, "weights"),
                   n_nodes=g["n_nodes"], d_feat=g["d_feat"],
                   dims=layer_dims(cfg))
