"""Unique random Selecting (paper §II-B Fig. 4a, §V-A Fig. 16).

Node-wise selection of k unique uniform neighbors per frontier node. The FPGA
draws one vertex per cycle from the *unsampled* bucket (set-partitioning keeps
the bucket compact) — uniqueness without a full-space scan or a synchronized
map.

TPU adaptation (DESIGN.md §2.2):

* ``floyd`` (default, paper-faithful semantics): Robert Floyd's k-unique-draw
  algorithm, vectorized over the whole frontier. Each of the k steps draws
  from the not-yet-sampled range and resolves collisions with a membership
  check — which is a set-counting compare-reduce over the current selection
  (k ≤ 25 comparators per node, the SCR in miniature). Exactly uniform
  k-subsets, no degree cap, k sequential steps (k is small and fixed).
* ``keysort``: attach a random key to each neighbor in a bounded window and
  take the top-k smallest — one pass, one sort.
  Exact when window ≥ max degree (set ``window`` accordingly in configs).
* ``reservoir``: the conventional baseline (paper Table IV) — sequential
  reservoir sampling, data-dependent loop bounded by ``window``. Kept for the
  benchmark comparison only.

All modes return neighbor *positions* within each node's CSC range plus the
gathered neighbor VIDs, padded with SENTINEL where degree < k.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .graph import CSC, SENTINEL


def _ranges(csc: CSC, frontier: jnp.ndarray):
    """(start, degree) per frontier node; sentinel/OOB nodes get degree 0."""
    nv = csc.n_nodes
    f = jnp.clip(frontier, 0, nv - 1)
    start = csc.ptr[f]
    deg = csc.ptr[f + 1] - start
    valid = (frontier >= 0) & (frontier < nv)
    deg = jnp.where(valid, deg, 0)
    return start.astype(jnp.int32), deg.astype(jnp.int32)


def select_floyd(csc: CSC, frontier: jnp.ndarray, k: int, key: jax.Array
                 ) -> jnp.ndarray:
    """Floyd's k unique uniform draws, vectorized over [F] frontier nodes.

    Returns neighbor VIDs [F, k] (SENTINEL-padded when deg < k).
    """
    start, deg = _ranges(csc, frontier)
    f = frontier.shape[0]
    sel = jnp.full((f, k), -1, jnp.int32)  # selected positions

    def body(i, carry):
        sel, key = carry
        key, sub = jax.random.split(key)
        u = jax.random.uniform(sub, (f,))
        j = deg - k + i  # Floyd index (valid when deg >= k)
        t = jnp.floor(u * (j + 1).astype(jnp.float32)).astype(jnp.int32)
        t = jnp.clip(t, 0, jnp.maximum(j, 0))
        # membership check = k-wide compare-reduce (SCR with == comparators)
        member = jnp.any(sel == t[:, None], axis=1)
        floyd_pick = jnp.where(member, j, t)
        # deg < k: take position i while i < deg, else invalid
        small_pick = jnp.where(i < deg, i, -1)
        pick = jnp.where(deg >= k, floyd_pick, small_pick)
        sel = sel.at[:, i].set(pick)
        return sel, key

    sel, _ = jax.lax.fori_loop(0, k, body, (sel, key))
    nbr_pos = start[:, None] + sel
    nbrs = jnp.take(csc.idx, jnp.clip(nbr_pos, 0, csc.idx.shape[0] - 1),
                    mode="clip")
    return jnp.where(sel >= 0, nbrs, SENTINEL)


def select_keysort(csc: CSC, frontier: jnp.ndarray, k: int, key: jax.Array,
                   window: int = 1024) -> jnp.ndarray:
    """Random-key top-k over a bounded neighbor window (one-pass, UPE-adapted).

    Exactly uniform when window >= max degree; otherwise restricted to the
    first ``window`` neighbors (documented bias — raise window per config).
    """
    start, deg = _ranges(csc, frontier)
    f = frontier.shape[0]
    offs = jnp.arange(window, dtype=jnp.int32)[None, :]  # [1, W]
    mask = offs < jnp.minimum(deg, window)[:, None]  # [F, W]
    pos = start[:, None] + offs
    r = jax.random.uniform(key, (f, window))
    r = jnp.where(mask, r, 2.0)  # invalid slots sort last
    # top-k smallest keys = uniform k-subset
    _, idx = jax.lax.top_k(-r, k)  # [F, k]
    picked_valid = jnp.take_along_axis(mask, idx, axis=1)
    picked_pos = jnp.take_along_axis(pos, idx, axis=1)
    nbrs = jnp.take(csc.idx, jnp.clip(picked_pos, 0, csc.idx.shape[0] - 1),
                    mode="clip")
    return jnp.where(picked_valid, nbrs, SENTINEL)


def select_reservoir(csc: CSC, frontier: jnp.ndarray, k: int, key: jax.Array,
                     window: int = 1024) -> jnp.ndarray:
    """Conventional reservoir sampling baseline — serial in the degree."""
    start, deg = _ranges(csc, frontier)
    f = frontier.shape[0]
    res = jnp.where(
        (jnp.arange(k, dtype=jnp.int32)[None, :] < deg[:, None]),
        jnp.arange(k, dtype=jnp.int32)[None, :], -1)

    def body(i, carry):
        res, key = carry
        key, sub = jax.random.split(key)
        u = jax.random.uniform(sub, (f,))
        j = jnp.floor(u * (i + 1)).astype(jnp.int32)  # uniform in [0, i]
        active = i < deg  # element i exists for this node
        take = active & (j < k)
        # res[n, j[n]] = i where take — one sequential reservoir step
        upd = jax.vmap(lambda r, jj, t: jnp.where(
            t, r.at[jj].set(i), r))(res, j, take)
        return upd, key

    res, _ = jax.lax.fori_loop(k, window, body, (res, key))
    pos = start[:, None] + res
    nbrs = jnp.take(csc.idx, jnp.clip(pos, 0, csc.idx.shape[0] - 1),
                    mode="clip")
    return jnp.where(res >= 0, nbrs, SENTINEL)


def select_layerwise(csc: CSC, frontier: jnp.ndarray, k: int, key: jax.Array,
                     window: int = 64) -> jnp.ndarray:
    """Layer-wise selection (paper §V-A): the whole frontier's neighborhoods
    aggregate into ONE candidate array and k nodes are drawn per layer (not
    per node) — fewer steps, no interconnection requirement.

    Static-shape aggregation: up to ``window`` neighbors per frontier node
    are gathered (positions chosen by random offset into each node's range
    so high-degree nodes aren't truncated deterministically), then one
    keysort top-k over the union — a single UPE partition pass.
    Returns [k] node ids (SENTINEL-padded if the union is smaller than k).
    """
    start, deg = _ranges(csc, frontier)
    f = frontier.shape[0]
    k1, k2 = jax.random.split(key)
    # random window start per node → unbiased coverage of long lists
    max_start = jnp.maximum(deg - window, 0)
    off0 = jnp.floor(jax.random.uniform(k1, (f,)) *
                     (max_start + 1).astype(jnp.float32)).astype(jnp.int32)
    offs = off0[:, None] + jnp.arange(window, dtype=jnp.int32)[None, :]
    valid = offs < deg[:, None]
    pos = start[:, None] + offs
    cand = jnp.take(csc.idx, jnp.clip(pos, 0, csc.idx.shape[0] - 1),
                    mode="clip")
    cand = jnp.where(valid, cand, SENTINEL).reshape(-1)  # the union array
    # the union is a SET: a node adjacent to several frontier nodes appears
    # once — sort + mask repeats, then draw (unique random Selecting)
    cand = jnp.sort(cand)
    dup = jnp.concatenate([jnp.zeros((1,), bool), cand[1:] == cand[:-1]])
    cand = jnp.where(dup, SENTINEL, cand)
    r = jax.random.uniform(k2, cand.shape)
    r = jnp.where(cand != SENTINEL, r, 2.0)
    _, ix = jax.lax.top_k(-r, k)  # k uniform draws from the union
    picked = jnp.take(cand, ix)
    return picked  # [k]


_SELECTORS = {
    "floyd": select_floyd,
    "keysort": select_keysort,
    "reservoir": select_reservoir,
}


def sample_layerwise(csc: CSC, batch_nodes: jnp.ndarray,
                     layer_sizes: tuple[int, ...], key: jax.Array,
                     window: int = 64
                     ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Layer-wise k-hop sampling (paper Fig. 4a right / §V-A).

    Each layer draws ``layer_sizes[l]`` nodes from the union of the current
    frontier's neighborhoods; edges connect every frontier node to the
    sampled nodes it actually neighbors (membership = set-counting).
    Returns (nodes, edge_dst, edge_src) like sample_khop.
    """
    from .set_count import rank_in_sorted
    frontier = batch_nodes.astype(jnp.int32)
    nodes = [frontier]
    e_dst, e_src = [], []
    for l, k_l in enumerate(layer_sizes):
        kl = jax.random.fold_in(key, l)
        picked = select_layerwise(csc, frontier, k_l, kl, window=window)
        # edges: frontier node → picked node wherever the edge exists;
        # membership test via sorted ranks over each node's neighbor range
        start, deg = _ranges(csc, frontier)
        sp = jnp.sort(picked)
        f = frontier.shape[0]
        offs = jnp.arange(window, dtype=jnp.int32)[None, :]
        valid = offs < jnp.minimum(deg, window)[:, None]
        pos = start[:, None] + offs
        nbr = jnp.take(csc.idx, jnp.clip(pos, 0, csc.idx.shape[0] - 1),
                       mode="clip")
        nbr = jnp.where(valid, nbr, SENTINEL)
        r = rank_in_sorted(sp, nbr.reshape(-1)).reshape(f, window)
        hit = jnp.take(sp, jnp.clip(r, 0, k_l - 1)) == nbr
        e_dst.append(jnp.where(hit, frontier[:, None],
                               SENTINEL).reshape(-1))
        e_src.append(jnp.where(hit, nbr, SENTINEL).reshape(-1))
        nodes.append(picked)
        frontier = picked
    return (jnp.concatenate(nodes), jnp.concatenate(e_dst),
            jnp.concatenate(e_src))


def sample_khop(csc: CSC, batch_nodes: jnp.ndarray, fanouts: tuple[int, ...],
                key: jax.Array, selection: str = "floyd", window: int = 1024
                ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Node-wise k-hop expansion (paper Fig. 4a).

    Returns (all_nodes [N_tot], edge_dst [E_tot], edge_src [E_tot]) in
    original VIDs, SENTINEL-padded. Duplicate vertices across parents are
    kept — Reindexing dedups them, exactly as the paper notes (§II-B).
    Edge direction: sampled neighbor (child) is the *source*, the frontier
    node is the *destination* (messages flow child → parent).
    """
    sel_fn = _SELECTORS[selection]
    if selection in ("keysort", "reservoir"):
        sel_fn = partial(sel_fn, window=window)

    frontier = batch_nodes.astype(jnp.int32)
    nodes = [frontier]
    e_dst, e_src = [], []
    for l, k_l in enumerate(fanouts):
        kl = jax.random.fold_in(key, l)
        nbrs = sel_fn(csc, frontier, k_l, kl)  # [F, k_l]
        parents = jnp.repeat(frontier, k_l)
        children = nbrs.reshape(-1)
        e_dst.append(parents)
        e_src.append(children)
        nodes.append(children)
        frontier = children
    all_nodes = jnp.concatenate(nodes)
    return all_nodes, jnp.concatenate(e_dst), jnp.concatenate(e_src)
