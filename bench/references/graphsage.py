"""Plain reference of one GraphSAGE serving request, in ``jax.numpy`` and
float32 at ``highest`` matmul precision (a control may ask for less).

What a request computes (GraphSAGE, arXiv:1706.02216, minibatch form, mean
aggregator), as the serving system defines it:

1. Sampling. The request's seed row is padded to ``seed_cap`` with
   SENTINEL. Hop ``l`` draws ``fanouts[l]`` neighbours for every entry of
   the frontier with the key ``fold_in(request_key, l)``, by Floyd's
   algorithm over the node's in-edge positions (the CSC column, sources
   ascending): step ``i`` splits the key, draws ``u`` uniform per entry and
   takes ``t = floor(u * (j + 1))`` with ``j = deg - k + i``, or ``j`` if
   ``t`` was taken before. A node of degree below ``k`` takes positions
   ``0 .. deg - 1``; the rest, and every draw of a SENTINEL entry, is
   SENTINEL. The next frontier is the flat list of draws.
   ``request_key = fold_in(PRNGKey(key_seed), request_id)``.
2. The sampled subgraph has one node per distinct node id drawn and one
   edge ``child -> parent`` per valid draw (repeats kept), so a node drawn
   several times gathers the edges of all its draws.
3. Layer ``l``: ``h' = h @ w_self + mean_{in-edges}(h_src) @ w_nb + b``;
   between layers ReLU and division by the row's L2 norm (at least 1e-6).
   The seeds' logits are ``h_L @ head``.

Nodes are not renumbered here: every list position keeps its node id, and
the edges into a node are found by comparing ids, so the reference shares
no sort, reindexing or pointer code with the program.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

SENTINEL = 0x7FFFFFFF


def _floyd(ptr, idx, n_nodes, frontier, k, key):
    """[F] frontier -> [F, k] neighbour ids (SENTINEL where none)."""
    valid = (frontier >= 0) & (frontier < n_nodes)
    f = jnp.clip(frontier, 0, n_nodes - 1)
    start = ptr[f]
    deg = jnp.where(valid, ptr[f + 1] - start, 0)
    sel = jnp.full((frontier.shape[0], k), -1, jnp.int32)
    for i in range(k):
        key, sub = jax.random.split(key)
        u = jax.random.uniform(sub, (frontier.shape[0],))
        j = deg - k + i
        t = jnp.floor(u * (j + 1).astype(jnp.float32)).astype(jnp.int32)
        t = jnp.clip(t, 0, jnp.maximum(j, 0))
        taken = jnp.any(sel == t[:, None], axis=1)
        big = jnp.where(taken, j, t)
        small = jnp.where(i < deg, i, -1)
        sel = sel.at[:, i].set(jnp.where(deg >= k, big, small))
    pos = jnp.clip(start[:, None] + sel, 0, idx.shape[0] - 1)
    return jnp.where(sel >= 0, idx[pos], SENTINEL)


def _one_request(ptr, idx, feats, params, seeds, key, *, n_nodes, fanouts,
                 dtype):
    """Logits [seed_cap, n_classes] of one padded seed row."""
    frontier, nodes, dst, src = seeds, [seeds], [], []
    for l, k in enumerate(fanouts):
        nb = _floyd(ptr, idx, n_nodes, frontier, k,
                    jax.random.fold_in(key, l))
        dst.append(jnp.repeat(frontier, k))
        src.append(nb.reshape(-1))
        nodes.append(nb.reshape(-1))
        frontier = nb.reshape(-1)
    nodes, dst, src = (jnp.concatenate(x) for x in (nodes, dst, src))
    cap = seeds.shape[0]
    edge_ok = (src != SENTINEL) & (dst != SENTINEL)
    # into[p, e]: edge e ends at the node held at list position p
    into = ((dst[None, :] == nodes[:, None]) & edge_ok[None, :]).astype(dtype)
    deg = jnp.maximum(jnp.sum(into, axis=1, keepdims=True), 1)
    # the source of edge e is the draw at list position cap + e
    h = feats[jnp.clip(nodes, 0, n_nodes - 1)].astype(dtype)
    for i, lp in enumerate(params["layers"]):
        agg = (into @ h[cap:]) / deg
        h = (h @ lp["w_self"].astype(dtype) + agg @ lp["w_nb"].astype(dtype)
             + lp["b"].astype(dtype))
        if i < len(params["layers"]) - 1:
            h = jax.nn.relu(h)
            h = h / jnp.maximum(jnp.linalg.norm(h, axis=-1, keepdims=True),
                                1e-6)
    return (h[:cap] @ params["head"].astype(dtype)).astype(jnp.float32)


@partial(jax.jit, static_argnames=("n_nodes", "fanouts", "dtype",
                                   "precision"))
def request_logits(ptr, idx, feats, params, seed_rows, keys, *, n_nodes,
                   fanouts, dtype=jnp.float32, precision="highest"):
    """Logits [B, seed_cap, n_classes] of B padded seed rows."""
    with jax.default_matmul_precision(precision):
        return jax.vmap(partial(_one_request, ptr, idx, feats, params,
                                n_nodes=n_nodes, fanouts=fanouts,
                                dtype=dtype))(seed_rows, keys)


def request_key(key_seed: int, rid: int) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(key_seed), rid)
