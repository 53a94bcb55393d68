"""Plain reference of whole-graph GAT inference, in ``jax.numpy`` and
float32 at ``highest`` matmul precision (a control may ask for less).

What it computes (GAT, arXiv:1710.10903, as PyG's ``GATConv`` with
``add_self_loops`` and its OGB example ``ogbn_products_gat.py`` stack it),
for every node ``i`` of the graph and each layer with input ``h``:

1. ``z = h W``, split into H heads of D;
2. ``e_ij = LeakyReLU_0.2(a_src . z_j + a_dst . z_i)`` per head, for every
   in-edge ``j -> i`` of the graph that is not a self loop (duplicates
   kept, each a term of its own) and for one self loop ``i -> i``;
3. ``alpha_ij = exp(e_ij - max_j e_ij) / sum_j exp(e_ij - max_j e_ij)``,
   the two-pass softmax over those terms;
4. ``out_i = merge_h(sum_j alpha_ij z_j) + b + h_i W_skip + b_skip``, where
   merge concatenates the heads, or averages them in the last layer;
   ELU between layers.

The logits are the last layer's output. Departures from PyG: dropout is
off (inference), and the logits are returned before ``log_softmax``,
which keeps their order.

The graph's self loops are taken out of the edge list first (their dst
set to SENTINEL), and ``references/csc.plain_csc`` (one lexicographic sort
and a search per column) makes the CSC of what is left, so every entry of
a node's column is a term. Each node's terms are laid out densely: nodes
are grouped by in-degree into widths of 64 times powers of four, each
node's row holds its in-edges (one slice of ``idx``, which is padded so
that no slice runs past its end) and then its self loop, and the softmax
and the weighted sum are reductions along that row. (A segment sum over
all 126M edges at once would be a scatter, which a TPU runs row by row.)
A batch of rows gets its input rows from the host for the skip and
leaves as finished output rows; each layer's input and output stay on
the host, so the device holds the graph, that layer's z and one batch.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.references.csc import plain_csc

SLOTS = 1 << 19          # edge slots of one batch of rows
PROJECT_ROWS = 1 << 16
WIDTHS = 4               # row widths are MIN_WIDTH times powers of this
MIN_WIDTH = 64
SENTINEL = 0x7FFFFFFF


@partial(jax.jit, static_argnames=("n_nodes",))
def _graph(dst, src, *, n_nodes):
    """(ptr, idx) of the edges that are not self loops."""
    return plain_csc(jnp.where(dst == src, SENTINEL, dst), src,
                     n_nodes=n_nodes)


@partial(jax.jit, static_argnames=("pad",))
def _padded(idx, *, pad):
    return jnp.concatenate([idx, jnp.zeros((pad,), idx.dtype)])


@partial(jax.jit, static_argnames=("precision",))
def _project(h, w, *, precision):
    with jax.default_matmul_precision(precision):
        return h @ w


@partial(jax.jit, donate_argnums=(0,))
def _put(z, zt, at):
    return jax.lax.dynamic_update_slice_in_dim(z, zt, at, 0)


@partial(jax.jit, static_argnames=("width", "last", "precision"))
def _rows(z, idx, lp, h, node, first, deg, *, width, last, precision):
    """Output rows [R, d_out] of the nodes ``node`` [R], whose input rows
    are ``h`` and whose in-edges are ``idx[first : first + deg]``, each
    read as ``width`` entries from where its edges start, with one more
    term for its self loop."""
    heads, d = lp["a_src"].shape
    src = jax.vmap(lambda a: jax.lax.dynamic_slice_in_dim(idx, a, width))(
        first)                                                    # [R, K]
    ok = jnp.arange(width) < deg[:, None]
    zs = z[jnp.where(ok, src, 0)].reshape(src.shape + (heads, d))
    zn = z[node].reshape(len(node), heads, d)
    with jax.default_matmul_precision(precision):
        at_dst = jnp.einsum("rhd,hd->rh", zn, lp["a_dst"])
        e = jax.nn.leaky_relu(jnp.einsum("rkhd,hd->rkh", zs, lp["a_src"])
                              + at_dst[:, None], 0.2)
        e_self = jax.nn.leaky_relu(jnp.einsum("rhd,hd->rh", zn, lp["a_src"])
                                   + at_dst, 0.2)
        skip = h @ lp["w_skip"] + lp["b_skip"] if "w_skip" in lp else 0
    e = jnp.concatenate([jnp.where(ok[..., None], e, -jnp.inf),
                         e_self[:, None]], axis=1)                # [R,K+1,H]
    p = jnp.exp(e - jnp.max(e, axis=1, keepdims=True))
    alpha = p / jnp.sum(p, axis=1, keepdims=True)
    agg = (jnp.sum(alpha[:, :-1, :, None] * zs, axis=1)
           + alpha[:, -1, :, None] * zn)                          # [R, H, D]
    out = (jnp.mean(agg, axis=1) if last else agg.reshape(len(node), -1)
           ) + lp["b"] + skip
    return out if last else jax.nn.elu(out)


def batches(ptr: np.ndarray):
    """(nodes [R], real rows, width K) of every node, grouped by
    in-degree: K is the least MIN_WIDTH * WIDTHS^i at or above the node's
    in-degree, and R * K = SLOTS where the width allows."""
    deg = np.diff(ptr)
    steps = np.ceil(np.log(np.maximum(deg, MIN_WIDTH) / MIN_WIDTH)
                    / np.log(WIDTHS) - 1e-9).astype(np.int64)
    width = MIN_WIDTH * WIDTHS ** np.maximum(steps, 0)
    for k in np.unique(width):
        nodes = np.flatnonzero(width == k).astype(np.int32)
        r = max(1, SLOTS // int(k))
        for a in range(0, len(nodes), r):
            part = np.full(r, nodes[-1], np.int32)
            part[:len(nodes[a:a + r])] = nodes[a:a + r]
            yield part, min(r, len(nodes) - a), int(k)


def _host_rows(h: np.ndarray, a: int, n: int) -> jnp.ndarray:
    """Rows ``a .. a + n`` of ``h``, zero past its end."""
    part = h[a:a + n]
    if len(part) < n:
        part = np.concatenate([part, np.zeros((n - len(part),) + h.shape[1:],
                                              h.dtype)])
    return jnp.asarray(part)


def logits(dst, src, feats, params, *, n_nodes: int, dtype=jnp.float32,
           precision="highest") -> np.ndarray:
    """Logits [n_nodes, classes] float32 on the host, of the graph given as
    padded edge arrays (SENTINEL tail)."""
    layers = [jax.tree.map(lambda a: a.astype(dtype), lp)
              for lp in params["layers"]]
    ptr, idx = _graph(dst, src, n_nodes=n_nodes)
    ptr = np.asarray(ptr).astype(np.int64)
    deg = np.diff(ptr)
    plan = list(batches(ptr))
    idx = _padded(idx, pad=max(k for _, _, k in plan))
    h = np.asarray(feats.astype(dtype))
    for i, lp in enumerate(layers):
        last = i == len(layers) - 1
        z = jnp.zeros((n_nodes, lp["w"].shape[1]), dtype)
        for a in range(0, n_nodes, PROJECT_ROWS):
            zt = _project(_host_rows(h, a, PROJECT_ROWS), lp["w"],
                          precision=precision)
            z = _put(z, zt[:min(PROJECT_ROWS, n_nodes - a)], a)
        out = np.empty((n_nodes, lp["b"].shape[0]), h.dtype)
        pending = []
        for nodes, real, k in plan:
            pending.append((nodes[:real], _rows(
                z, idx, lp, jnp.asarray(h[nodes]), jnp.asarray(nodes),
                jnp.asarray(ptr[nodes].astype(np.int32)),
                jnp.asarray(deg[nodes].astype(np.int32)),
                width=k, last=last, precision=precision)))
            # the batch before is fetched while this one runs
            if len(pending) > 1:
                done, rows = pending.pop(0)
                out[done] = np.asarray(rows[:len(done)])
        done, rows = pending.pop()
        out[done] = np.asarray(rows[:len(done)])
        del z
        h = out
    return np.asarray(h, np.float32)
