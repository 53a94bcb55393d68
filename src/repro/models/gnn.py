"""GNN model zoo: GraphSAGE, GAT, GatedGCN, MeshGraphNet.

All four consume the layout AutoGNN's preprocessing produces: an edge list
sorted by destination (+ CSC pointer array when needed). Message passing is
edge-gather → segment-reduce — `jax.ops.segment_sum` in the portable path,
reductions over the CSC pointers when ``GraphBatch.ptr`` is set (the
scatter-free serving path), kernels/segment_agg.py (one-hot MXU matmul) in
the Pallas path. SENTINEL edges (padding / dropped samples) are masked out
of every reduction. ``gat_infer`` runs a GAT over a whole graph's CSC,
layer by layer.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from .common import Params, dense_init, layer_norm, mlp_apply, mlp_init

SEN = jnp.int32(0x7FFFFFFF)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GraphBatch:
    """Static-shape graph minibatch (block-diagonal for batched graphs).

    ``ptr`` is optional: when set (the serving path builds it from the
    sampled subgraph's CSC), segment reductions run scatter-free over the
    pointer array instead of through ``jax.ops.segment_sum`` — a
    requirement of the ``gnn_serve`` HLO contract. It requires
    ``edge_dst`` sorted ascending with ``ptr[d] .. ptr[d+1]`` spanning
    node ``d``'s incoming edges, which is exactly the layout
    ``pipeline.sample_subgraph`` emits.
    """

    edge_dst: jnp.ndarray  # [E] int32, sorted ascending, SENTINEL pad
    edge_src: jnp.ndarray  # [E] int32
    node_feat: jnp.ndarray  # [N, Df] float
    labels: jnp.ndarray  # [N] int32 or [N, Do]/[G, Do] float
    label_mask: jnp.ndarray  # [N] or [G] bool
    edge_feat: jnp.ndarray | None = None  # [E, De]
    graph_ids: jnp.ndarray | None = None  # [N] int32 (batched graphs)
    ptr: jnp.ndarray | None = None  # [N+1] int32 CSC pointers (serve path)
    n_graphs: int = 1

    def tree_flatten(self):
        return ((self.edge_dst, self.edge_src, self.node_feat, self.labels,
                 self.label_mask, self.edge_feat, self.graph_ids, self.ptr),
                (self.n_graphs,))

    @classmethod
    def tree_unflatten(cls, aux, ch):
        return cls(*ch, n_graphs=aux[0])

    @property
    def n_nodes(self) -> int:
        return self.node_feat.shape[0]


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    name: str
    kind: str  # graphsage | gat | gatedgcn | meshgraphnet
    n_layers: int
    d_hidden: int
    n_heads: int = 1
    aggregator: str = "mean"
    mlp_layers: int = 2
    sample_sizes: tuple[int, ...] = ()
    d_out: int = 0  # regression output dim (0 → classification)
    dtype: Any = jnp.float32
    use_pallas_agg: bool = False
    out_heads: int = 1      # GAT: attention heads of the last layer,
                            # which it averages
    skip: bool = False      # GAT: each layer adds a linear map of its input


# ------------------------------------------------------ segment reductions
def _valid(batch: GraphBatch):
    return batch.edge_dst < batch.n_nodes


def _ptr_seg_sum(ptr: jnp.ndarray, msgs: jnp.ndarray) -> jnp.ndarray:
    """Scatter-free segment sum over CSC pointers: cumulative-sum the
    (already masked) message stream once, then gather the prefix
    differences at each node's ``ptr`` span. Float summation order differs
    from ``segment_sum``'s, so the two are numerically close but not
    bit-equal — the serve path uses this function on BOTH its batched and
    sequential legs, which is what makes those two bit-identical."""
    cs = jnp.cumsum(msgs.astype(jnp.float32), axis=0)
    cs = jnp.concatenate([jnp.zeros((1,) + cs.shape[1:], cs.dtype), cs],
                         axis=0)
    p = jnp.clip(ptr, 0, msgs.shape[0])
    return (jnp.take(cs, p[1:], axis=0)
            - jnp.take(cs, p[:-1], axis=0)).astype(msgs.dtype)


def seg_sum(batch: GraphBatch, msgs: jnp.ndarray,
            pallas: bool = False) -> jnp.ndarray:
    """Σ over incoming edges per dst node; SENTINEL edges contribute 0."""
    valid = _valid(batch)[:, None]
    msgs = jnp.where(valid, msgs, 0)
    if pallas:
        from repro.kernels.ops import segment_sum_padded
        return segment_sum_padded(batch.edge_dst, msgs.astype(jnp.float32),
                                  batch.n_nodes).astype(msgs.dtype)
    if batch.ptr is not None:
        return _ptr_seg_sum(batch.ptr, msgs)
    dst = jnp.minimum(batch.edge_dst, batch.n_nodes - 1)
    return jax.ops.segment_sum(msgs, dst, num_segments=batch.n_nodes)


def seg_mean(batch: GraphBatch, msgs: jnp.ndarray,
             pallas: bool = False) -> jnp.ndarray:
    s = seg_sum(batch, msgs, pallas)
    ones = jnp.ones((batch.edge_dst.shape[0], 1), msgs.dtype)
    deg = seg_sum(batch, ones, pallas)
    return s / jnp.maximum(deg, 1.0)


SCAN_BLOCK = 128   # edges a block of the segmented scan


def _seg_scan(flags, vals, op, empty, axis: int):
    """Inclusive scan of ``vals`` along ``axis`` under ``op``, restarting
    where ``flags`` is set, by log2(n) doubling shifts of static slices
    (a TPU compiles a strided odd/even scan in time that grows with n)."""
    n = vals.shape[axis]

    def shifted(a, k, fill):
        head = jnp.full_like(jax.lax.slice_in_dim(a, 0, k, axis=axis), fill)
        return jnp.concatenate(
            [head, jax.lax.slice_in_dim(a, 0, n - k, axis=axis)], axis=axis)

    k = 1
    while k < n:
        vals = jnp.where(flags, vals, op(vals, shifted(vals, k, empty)))
        flags = flags | shifted(flags, k, False)
        k *= 2
    return flags, vals


def _ptr_seg_reduce(batch: GraphBatch, vals: jnp.ndarray, op, empty
                    ) -> jnp.ndarray:
    """Scatter-free max or sum per destination over CSC pointers: a
    segmented scan along the dst-sorted edges (restarting where
    ``edge_dst`` changes), read at each node's last edge. The scan runs
    inside blocks of SCAN_BLOCK edges, then over the blocks' totals, and
    each block takes the total before it where its first segment goes
    on. Each total combines only its own segment's values. Nodes without
    edges get the scan's value at a neighbour's edge; callers mask them."""
    dst = batch.edge_dst
    e = dst.shape[0]
    pad = -e % SCAN_BLOCK
    start = jnp.concatenate([jnp.ones((1,), bool), dst[1:] != dst[:-1],
                             jnp.ones((pad,), bool)])
    vals = jnp.concatenate(
        [vals, jnp.full((pad,) + vals.shape[1:], empty, vals.dtype)])
    blocks = (-1, SCAN_BLOCK) + vals.shape[1:]
    f, run = _seg_scan(start.reshape(blocks[:2] + (1,) * (vals.ndim - 1)),
                       vals.reshape(blocks), op, empty, 1)
    _, ends = _seg_scan(f[:, -1], run[:, -1], op, empty, 0)
    before = jnp.concatenate([jnp.full_like(ends[:1], empty), ends[:-1]])
    run = jnp.where(f, run, op(run, before[:, None])).reshape(vals.shape)
    last = jnp.clip(batch.ptr[1:batch.n_nodes + 1] - 1, 0, e - 1)
    return jnp.take(run, last, axis=0)


def seg_reduce(batch: GraphBatch, vals: jnp.ndarray, valid: jnp.ndarray,
               maximum: bool) -> jnp.ndarray:
    """Max (-inf where none) or sum (0 where none) over each destination's
    ``valid`` in-edges; scatter-free when ``batch.ptr`` is set, and then
    each sum adds only its own segment's values."""
    empty = -jnp.inf if maximum else 0.0
    vals = jnp.where(valid, vals, empty)
    if batch.ptr is None:
        dst = jnp.minimum(batch.edge_dst, batch.n_nodes - 1)
        seg = jax.ops.segment_max if maximum else jax.ops.segment_sum
        return seg(vals, dst, num_segments=batch.n_nodes)
    out = _ptr_seg_reduce(batch, vals, jnp.maximum if maximum else jnp.add,
                          empty)
    deg = batch.ptr[1:batch.n_nodes + 1] - batch.ptr[:batch.n_nodes]
    return jnp.where((deg > 0).reshape((-1,) + (1,) * (vals.ndim - 1)), out,
                     empty)


def seg_softmax(batch: GraphBatch, scores: jnp.ndarray, valid: jnp.ndarray,
                self_scores: jnp.ndarray
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Edge softmax per destination over its ``valid`` in-edges and one
    term of its own (``self_scores`` [N, H], the self loop). Returns the
    weights of the edges [E, H] (0 on invalid ones) and of the self loops
    [N, H]. Scatter-free when ``batch.ptr`` is set."""
    m = jnp.maximum(seg_reduce(batch, scores, valid, True), self_scores)
    p = softmax_terms(jnp.where(valid, scores, -jnp.inf), gather_dst(batch, m))
    p_self = softmax_terms(self_scores, m)
    den = seg_reduce(batch, p, valid, False) + p_self
    return p / gather_dst(batch, den), p_self / den


def gather_src(batch: GraphBatch, h: jnp.ndarray) -> jnp.ndarray:
    src = jnp.minimum(batch.edge_src, batch.n_nodes - 1)
    return jnp.take(h, src, axis=0)


def gather_dst(batch: GraphBatch, h: jnp.ndarray) -> jnp.ndarray:
    dst = jnp.minimum(batch.edge_dst, batch.n_nodes - 1)
    return jnp.take(h, dst, axis=0)


# ------------------------------------------------------------- GraphSAGE
def _sage_init(cfg: GNNConfig, key, d_in: int) -> Params:
    layers = []
    d = d_in
    for i in range(cfg.n_layers):
        k1, k2, key = jax.random.split(key, 3)
        layers.append({
            "w_self": dense_init(k1, d, cfg.d_hidden, cfg.dtype),
            "w_nb": dense_init(k2, d, cfg.d_hidden, cfg.dtype),
            "b": jnp.zeros((cfg.d_hidden,), cfg.dtype),
        })
        d = cfg.d_hidden
    return {"layers": layers}


def _sage_apply(cfg: GNNConfig, p: Params, batch: GraphBatch) -> jnp.ndarray:
    h = batch.node_feat.astype(cfg.dtype)
    for i, lp in enumerate(p["layers"]):
        msgs = gather_src(batch, h)
        agg = (seg_mean(batch, msgs, cfg.use_pallas_agg)
               if cfg.aggregator == "mean"
               else seg_sum(batch, msgs, cfg.use_pallas_agg))
        h = h @ lp["w_self"] + agg @ lp["w_nb"] + lp["b"]
        if i < cfg.n_layers - 1:
            h = jax.nn.relu(h)
            h = h / jnp.maximum(
                jnp.linalg.norm(h, axis=-1, keepdims=True), 1e-6)
    return h


# ------------------------------------------------------------------- GAT
# GATConv (arXiv:1710.10903, as PyG implements it) with an optional skip:
#   z = h W (H heads of D);  e_ij = LeakyReLU_0.2(a_src.z_j + a_dst.z_i)
#   alpha_ij = softmax of e_ij over j in N_in(i) + {i}
#   out_i = merge_h(sum_j alpha_ij z_j) + b [+ h_i W_skip + b_skip]
# merge is concat, or the mean over heads in the last layer (GATConv's
# concat=False, as the paper's output layer averages its heads);
# ELU between layers. The graph's own self loops are dropped and one loop
# per node is added; duplicate edges are kept. The sampled layer below
# and the whole-graph layer (``gat_infer``) share these functions.
def _gat_init(cfg: GNNConfig, key, d_in: int, n_out: int) -> Params:
    layers = []
    d = d_in
    for i in range(cfg.n_layers):
        last = i == cfg.n_layers - 1
        heads = cfg.out_heads if last else cfg.n_heads
        width = (n_out or cfg.d_hidden) if last else cfg.d_hidden
        d_next = width if last else heads * width
        k1, k2, k3, k4, key = jax.random.split(key, 5)
        lp = {
            "w": dense_init(k1, d, heads * width, cfg.dtype),
            "a_src": (jax.random.normal(k2, (heads, width)) * 0.1
                      ).astype(cfg.dtype),
            "a_dst": (jax.random.normal(k3, (heads, width)) * 0.1
                      ).astype(cfg.dtype),
            "b": jnp.zeros((d_next,), cfg.dtype),
        }
        if cfg.skip:
            lp["w_skip"] = dense_init(k4, d, d_next, cfg.dtype)
            lp["b_skip"] = jnp.zeros((d_next,), cfg.dtype)
        layers.append(lp)
        d = d_next
    return {"layers": layers}


def gat_project(lp: Params, h: jnp.ndarray):
    """(z [N, H, D], a_src.z [N, H], a_dst.z [N, H]) of node rows ``h``."""
    heads, width = lp["a_src"].shape
    z = (h @ lp["w"]).reshape(h.shape[0], heads, width)
    return (z, jnp.einsum("nhd,hd->nh", z, lp["a_src"]),
            jnp.einsum("nhd,hd->nh", z, lp["a_dst"]))


def gat_skip(lp: Params, h: jnp.ndarray, d_out: int) -> jnp.ndarray:
    """The layer's skip term of node rows ``h`` (0 without a skip)."""
    if "w_skip" not in lp:
        return jnp.zeros((h.shape[0], d_out), h.dtype)
    return h @ lp["w_skip"] + lp["b_skip"]


def gat_score(s_src: jnp.ndarray, s_dst: jnp.ndarray) -> jnp.ndarray:
    """Attention logits of edges from their two terms."""
    return jax.nn.leaky_relu(s_src + s_dst, 0.2)


def softmax_terms(scores: jnp.ndarray, m: jnp.ndarray) -> jnp.ndarray:
    """exp(score - m): m is the exact max of the score's segment (or of the
    part of it seen so far), so no term overflows and the largest is 1."""
    return jnp.exp(scores - m)


def gat_combine(lp: Params, agg: jnp.ndarray, skip: jnp.ndarray,
                last: bool) -> jnp.ndarray:
    """Layer output of rows from their attention sums ``agg`` [N, H, D] and
    their skip term: heads concatenated (averaged in the last layer),
    bias, skip, ELU between layers."""
    merged = (jnp.mean(agg, axis=1) if last
              else agg.reshape(agg.shape[0], -1))
    out = merged + lp["b"] + skip
    return out if last else jax.nn.elu(out)


def _gat_apply(cfg: GNNConfig, p: Params, batch: GraphBatch) -> jnp.ndarray:
    h = batch.node_feat.astype(cfg.dtype)
    valid = _valid(batch) & (batch.edge_src != batch.edge_dst)
    for i, lp in enumerate(p["layers"]):
        last = i == len(p["layers"]) - 1
        z, s_src, s_dst = gat_project(lp, h)
        scores = gat_score(gather_src(batch, s_src), gather_dst(batch, s_dst))
        alpha, alpha_self = seg_softmax(batch, scores, valid[:, None],
                                        gat_score(s_src, s_dst))
        msgs = gather_src(batch, z) * alpha[..., None]
        agg = (seg_reduce(batch, msgs, valid[:, None, None], False)
               + z * alpha_self[..., None])
        h = gat_combine(lp, agg, gat_skip(lp, h, lp["b"].shape[0]), last)
    return h


# ------------------------------------------------------- whole-graph GAT
# Layer-wise inference of every node of a graph from its CSC (edges sorted
# by destination, ``ptr`` per destination), as PyG's OGB example evaluates
# its GAT with full neighbourhoods. Rows are grouped in tiles of ROW_TILE;
# a tile's edges, one stretch of the CSC, are cut into chunks of at most
# EDGE_CHUNK, and each step of the edge loop takes CHUNKS_PER_STEP chunks.
# A chunk meets its tile's rows through a one-hot [ROW_TILE, EDGE_CHUNK]
# made by comparing edge positions with the tile's pointers (no scatter):
# it gives each row's exact max, its sum of terms and, as one matrix
# product, its attention-weighted sum of the gathered z rows. The chunks
# of one tile merge by the online softmax, and a tile still open at the
# end of a step carries its rows' (max, sum, acc) into the next. A
# finished tile is written over its own rows of the output buffer, which
# holds the skip terms the projection pass put there; for a hidden layer
# that buffer is the layer's input, so a layer needs one more [N, H*D]
# buffer (z) and not two.
#
# Sized for ogbn-products on one TPU v5e (16 GB): a step gathers 128 x 1024
# rows of z, 268 MB at 512 wide, beside the 5 GB of z and of the buffer; a
# tile of 16 rows holds ~800 edges, so most tiles take one chunk.
ROW_TILE = 16
EDGE_CHUNK = 1024
CHUNKS_PER_STEP = 128
PROJECT_ROWS = 16384
_EMPTY = -1e30          # the max of a row that has no term yet


def _chunk_list(ptr: jnp.ndarray, n_nodes: int, capacity: int, w: int,
                c: int, b: int):
    """Every tile's chunks in CSC order, then empty ones (a tile of their
    own past the last) up to a static length: per chunk (tile, first edge,
    end edge, index in its tile, last of its tile), then the number of
    real chunks and the number of rows whose edges lie in more than one
    chunk. Each tile has at least one chunk, so every row is finished."""
    n_tiles = -(-n_nodes // w)
    ends = jnp.take(ptr, jnp.minimum(jnp.arange(n_tiles + 1) * w, n_nodes))
    per_tile = jnp.maximum(1, -(-(ends[1:] - ends[:-1]) // c))
    first = jnp.cumsum(per_tile) - per_tile
    n_max = -(-(n_tiles + -(-capacity // c)) // b) * b
    item = jnp.arange(n_max, dtype=jnp.int32)
    tile = (jnp.searchsorted(first, item, side="right") - 1).astype(jnp.int32)
    total = jnp.sum(per_tile)
    real = item < total
    k = jnp.where(real, item - jnp.take(first, tile), 0)
    lo = jnp.where(real, jnp.take(ends, tile) + k * c, ends[-1])
    hi = jnp.minimum(lo + c, jnp.take(ends, tile + 1))
    hi = jnp.where(real, jnp.maximum(lo, hi), lo)
    # padding chunks make a tile of their own past the last
    tile = jnp.where(real, tile, n_tiles)
    last = jnp.concatenate([tile[1:] != tile[:-1], jnp.ones((1,), bool)])
    # a row is split where a chunk starts inside its edges; counted once
    row = jnp.searchsorted(ptr[:n_nodes + 1], lo, side="right") - 1
    cont = (k > 0) & (lo < hi) & (jnp.take(ptr, row) < lo)
    again = jnp.concatenate([jnp.zeros((1,), bool),
                             cont[:-1] & (row[:-1] == row[1:])])
    return (tile, lo, hi, k, last), total, jnp.sum(cont & ~again)


def _project_pass(lp: Params, h, out: jnp.ndarray, n_nodes: int,
                  rows: int):
    """z of every row, and each row's skip term written over ``out``, in
    tiles of ``rows``. With ``h`` None the input is ``out`` itself, read
    tile by tile before the tile is written."""
    heads, width = lp["a_src"].shape
    z = jnp.zeros((out.shape[0], heads * width), out.dtype)

    def body(i, carry):
        z, out = carry
        r = i * rows
        if h is None:
            x = jax.lax.dynamic_slice_in_dim(out, r, rows)
        else:
            # the last tile of the features is read where they end, so it
            # overlaps the one before: those rows come out the same twice
            r = jnp.minimum(r, h.shape[0] - rows)
            x = jax.lax.dynamic_slice_in_dim(h, r, rows)
        z = jax.lax.dynamic_update_slice_in_dim(
            z, (x @ lp["w"]).astype(z.dtype), r, 0)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, gat_skip(lp, x, out.shape[1]), r, 0)
        return z, out

    return jax.lax.fori_loop(0, -(-n_nodes // rows), body, (z, out))


def _merge(m1, l1, a1, m2, l2, a2):
    """Two online-softmax states of the same rows as one."""
    m = jnp.maximum(m1, m2)
    f1, f2 = softmax_terms(m1, m), softmax_terms(m2, m)
    return m, l1 * f1 + l2 * f2, a1 * f1[..., None] + a2 * f2[..., None]


def _edge_pass(lp: Params, z, out, ptr, idx, chunks, n_chunks,
               n_nodes: int, last_layer: bool, w: int, c: int, b: int):
    """Attention of every row over its in-edges (the graph's own self
    loops left out) and one self loop, combined with the skip term
    ``out`` holds and written over it tile by tile. Returns ``out`` and
    the number of graph edges attended. Per-edge arrays keep the edges on
    the minor axis ([b, H, c]), where a TPU lays them out densely."""
    heads, width = lp["a_src"].shape
    in_tile = jnp.arange(w)
    slot = jnp.arange(b)
    hp = jax.lax.Precision.HIGHEST
    # ptr[1:], long enough for the padding chunks' tile past the last
    tile_ends = jnp.pad(ptr, (0, z.shape[0] + w - ptr.shape[0]), mode="edge")

    def terms(rows, a):                  # a.z of rows [b, n, H*D] -> [b, H, n]
        # one product with a block-diagonal [H*D, 128]: head h's a in
        # rows h*D.. of column h, the other columns zero (lane-dense out)
        blocks = (jnp.eye(heads, 128, dtype=a.dtype)[:, None, :]
                  * a[..., None]).reshape(heads * width, 128)
        return jnp.swapaxes((rows @ blocks)[..., :heads], 1, 2)

    def step(i, carry):
        out, c_m, c_l, c_acc, n_edges = carry
        t, e0, e1, k, fin = (jax.lax.dynamic_slice_in_dim(a, i * b, b)
                             for a in chunks)
        # the tile's rows of z and where their edges end, as slices
        zs = jax.vmap(lambda a: jax.lax.dynamic_slice_in_dim(z, a, w))(
            t * w)                                                # [b,w,HD]
        ends = jax.vmap(lambda a: jax.lax.dynamic_slice_in_dim(
            tile_ends, a, w))(t * w + 1)                          # [b, w]
        # a chunk's stretch of the CSC is one slice of idx (one that would
        # run past its end starts earlier and masks the surplus)
        start = jnp.minimum(e0, idx.shape[0] - c)
        src = jax.vmap(lambda a: jax.lax.dynamic_slice_in_dim(idx, a, c))(
            start)                                                # [b, c]
        pos = start[:, None] + jnp.arange(c)
        # an edge's row in its tile: the rows whose edges end at or
        # before it; the graph's own self loops are left out
        r = jnp.minimum(jnp.sum(ends[:, :, None] <= pos[:, None], axis=1),
                        w - 1)
        edge = ((pos >= e0[:, None]) & (pos < e1[:, None])
                & (src != t[:, None] * w + r))
        hot = (r[:, None] == in_tile[:, None]) & edge[:, None]    # [b,w,c]
        hot4 = hot[:, None]
        zr = jnp.take(z, jnp.where(edge, src, 0), axis=0, mode="clip")
        dst_terms = terms(zs, lp["a_dst"])                        # [b,H,w]
        scores = gat_score(terms(zr, lp["a_src"]), jnp.sum(
            jnp.where(hot4, dst_terms[..., None], 0), axis=2))    # [b,H,c]
        # a tile's first chunk starts from its rows' self loops
        first = (k == 0)[:, None, None]
        m0 = jnp.where(first, gat_score(terms(zs, lp["a_src"]), dst_terms),
                       _EMPTY)
        m = jnp.maximum(m0, jnp.max(jnp.where(hot4, scores[:, :, None],
                                              _EMPTY), axis=3))
        p = jnp.where(edge[:, None], softmax_terms(scores, jnp.sum(
            jnp.where(hot4, m[..., None], 0), axis=2)), 0)
        f0 = softmax_terms(m0, m)
        l = first * f0 + jnp.sum(jnp.where(hot4, p[:, :, None], 0), axis=3)
        # one product per head: its terms weigh the one-hot, and the
        # product keeps that head's columns of the gathered rows
        hot_f = hot.astype(z.dtype)
        acc = (jnp.where(first[..., None], zs.reshape(b, w, heads, width), 0)
               * jnp.swapaxes(f0, 1, 2)[..., None]
               + jnp.stack([jnp.einsum(
                   "bwc,bcd->bwd", hot_f * p[:, j, None], zr)[
                       ..., j * width:(j + 1) * width]
                   for j in range(heads)], axis=2))
        m, l = jnp.swapaxes(m, 1, 2), jnp.swapaxes(l, 1, 2)       # [b,w,H]
        # the tile a step left open goes on in this one's first chunk
        on = ((slot == 0) & (k[0] > 0))[:, None, None]
        cm, cl, cacc = _merge(c_m, c_l, c_acc, m[0], l[0], acc[0])
        m = jnp.where(on, cm[None], m)
        l = jnp.where(on, cl[None], l)
        acc = jnp.where(on[..., None], cacc[None], acc)
        # chunks of one tile add up in its slot: tile t[0] + slot
        member = t[None, :] == t[0] + slot[:, None]               # [b, b]
        m_slot = jnp.max(jnp.where(member[..., None, None], m[None],
                                   _EMPTY), axis=1)
        f = softmax_terms(m, jnp.take(m_slot, t - t[0], axis=0))
        l_slot = jnp.einsum("sj,jwh->swh", member.astype(l.dtype), l * f,
                            precision=hp)
        acc_slot = jnp.einsum("sj,jx->sx", member.astype(acc.dtype),
                              (acc * f[..., None]).reshape(b, -1),
                              precision=hp).reshape(acc.shape)
        done = jnp.any(member & fin[None, :], axis=1)
        open_slot = t[b - 1] - t[0]
        c_m, c_l, c_acc = (jnp.take(a, open_slot, axis=0)
                           for a in (m_slot, l_slot, acc_slot))
        # finished tiles over their rows' skip terms
        r0 = t[0] * w
        old = jax.lax.dynamic_slice_in_dim(out, r0, b * w)
        agg = acc_slot / jnp.where(l_slot > 0, l_slot, 1)[..., None]
        new = gat_combine(lp, agg.reshape(b * w, heads, width), old,
                          last_layer)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(jnp.repeat(done, w)[:, None], new, old), r0, 0)
        return out, c_m, c_l, c_acc, n_edges + jnp.sum(edge)

    init = (out, jnp.full((w, heads), _EMPTY, z.dtype),
            jnp.zeros((w, heads), z.dtype),
            jnp.zeros((w, heads, width), z.dtype), jnp.int32(0))
    out, *_, n_edges = jax.lax.fori_loop(0, -(-n_chunks // b), step, init)
    return out, n_edges


def gat_infer(csc, feats: jnp.ndarray, params: Params, *, cfg: GNNConfig,
              _sizes: tuple[int, int, int, int] | None = None):
    """Logits [n_nodes, classes] of every node of the graph ``csc`` (as
    ``pipeline.convert`` gives it) under the GAT ``params``, computed
    layer by layer over the whole graph, with counters: ``edges`` attended
    per layer (self loops in), ``chunks`` per layer, ``split_segments``
    (rows whose edges span chunks). Scatter-free. ``_sizes`` (row tile,
    edge chunk, chunks per step, projection rows) is for tests."""
    from repro import scopes
    w, c, b, rows = _sizes or (ROW_TILE, EDGE_CHUNK, CHUNKS_PER_STEP,
                               PROJECT_ROWS)
    n = csc.n_nodes
    rows = min(rows, -(-n // w) * w)
    c = min(c, csc.idx.shape[0])
    n_buf = -(-n // rows) * rows + b * w
    ptr = csc.ptr[:n + 1]
    h = feats.astype(cfg.dtype)
    if h.shape[0] < rows:
        h = jnp.pad(h, ((0, rows - h.shape[0]), (0, 0)))
    with jax.named_scope(scopes.INFER_EDGE):
        chunks, n_chunks, split = _chunk_list(ptr, n, csc.idx.shape[0], w,
                                              c, b)
    out = None
    for i, lp in enumerate(params["layers"]):
        last = i == len(params["layers"]) - 1
        d_out = lp["b"].shape[0]
        with jax.named_scope(scopes.INFER_PROJECT):
            if out is not None and out.shape[1] == d_out:
                # a hidden layer's output goes over its input
                z, out = _project_pass(lp, None, out, n, rows)
            else:
                z, out = _project_pass(
                    lp, h if out is None else out,
                    jnp.zeros((n_buf, d_out), cfg.dtype), n, rows)
        with jax.named_scope(scopes.INFER_EDGE):
            out, n_edges = _edge_pass(lp, z, out, ptr, csc.idx, chunks,
                                      n_chunks, n, last, w, c, b)
            if i == 0:
                attended = n_edges + n
    with jax.named_scope(scopes.INFER_EDGE):
        return out[:n], {"edges": attended, "chunks": n_chunks,
                         "split_segments": split}


# -------------------------------------------------------------- GatedGCN
def _ggcn_init(cfg: GNNConfig, key, d_in: int, d_ein: int) -> Params:
    k_n, k_e, key = jax.random.split(key, 3)
    layers = []
    for _ in range(cfg.n_layers):
        ks = jax.random.split(key, 6)
        key = ks[5]
        d = cfg.d_hidden
        layers.append({
            "A": dense_init(ks[0], d, d, cfg.dtype),
            "B": dense_init(ks[1], d, d, cfg.dtype),
            "C": dense_init(ks[2], d, d, cfg.dtype),
            "U": dense_init(ks[3], d, d, cfg.dtype),
            "V": dense_init(ks[4], d, d, cfg.dtype),
            "ln_h_scale": jnp.ones((d,), cfg.dtype),
            "ln_h_bias": jnp.zeros((d,), cfg.dtype),
            "ln_e_scale": jnp.ones((d,), cfg.dtype),
            "ln_e_bias": jnp.zeros((d,), cfg.dtype),
        })
    return {
        "embed_n": dense_init(k_n, d_in, cfg.d_hidden, cfg.dtype),
        "embed_e": dense_init(k_e, max(d_ein, 1), cfg.d_hidden, cfg.dtype),
        "layers": layers,
    }


def _ggcn_apply(cfg: GNNConfig, p: Params, batch: GraphBatch) -> jnp.ndarray:
    h = batch.node_feat.astype(cfg.dtype) @ p["embed_n"]
    if batch.edge_feat is not None:
        e = batch.edge_feat.astype(cfg.dtype) @ p["embed_e"]
    else:
        e = jnp.zeros((batch.edge_dst.shape[0], cfg.d_hidden), cfg.dtype)
    for lp in p["layers"]:
        e_new = (gather_dst(batch, h @ lp["A"]) + gather_src(batch, h @ lp["B"])
                 + e @ lp["C"])
        gate = jax.nn.sigmoid(e_new)
        msg = gate * gather_src(batch, h @ lp["V"])
        num = seg_sum(batch, msg, cfg.use_pallas_agg)
        den = seg_sum(batch, gate, cfg.use_pallas_agg)
        h_new = h @ lp["U"] + num / (den + 1e-6)
        h = h + jax.nn.relu(
            layer_norm(h_new, lp["ln_h_scale"], lp["ln_h_bias"]))
        e = e + jax.nn.relu(
            layer_norm(e_new, lp["ln_e_scale"], lp["ln_e_bias"]))
    return h


# ---------------------------------------------------------- MeshGraphNet
def _mgn_init(cfg: GNNConfig, key, d_in: int, d_ein: int) -> Params:
    d = cfg.d_hidden
    ks = jax.random.split(key, 4 + cfg.n_layers * 2)
    mlp_dims = (d,) * cfg.mlp_layers
    layers = []
    for i in range(cfg.n_layers):
        layers.append({
            "edge_mlp": mlp_init(ks[4 + 2 * i], (3 * d,) + mlp_dims + (d,),
                                 cfg.dtype),
            "node_mlp": mlp_init(ks[5 + 2 * i], (2 * d,) + mlp_dims + (d,),
                                 cfg.dtype),
        })
    return {
        "enc_n": mlp_init(ks[0], (d_in,) + mlp_dims + (d,), cfg.dtype),
        "enc_e": mlp_init(ks[1], (max(d_ein, 1),) + mlp_dims + (d,),
                          cfg.dtype),
        "dec": mlp_init(ks[2], (d,) + mlp_dims + (max(cfg.d_out, 1),),
                        cfg.dtype),
        "layers": layers,
    }


def _mgn_apply(cfg: GNNConfig, p: Params, batch: GraphBatch) -> jnp.ndarray:
    h = mlp_apply(p["enc_n"], batch.node_feat.astype(cfg.dtype))
    if batch.edge_feat is not None:
        e = mlp_apply(p["enc_e"], batch.edge_feat.astype(cfg.dtype))
    else:
        e = jnp.zeros((batch.edge_dst.shape[0], cfg.d_hidden), cfg.dtype)
    for lp in p["layers"]:
        e = e + mlp_apply(lp["edge_mlp"], jnp.concatenate(
            [e, gather_src(batch, h), gather_dst(batch, h)], axis=-1))
        agg = seg_sum(batch, e, cfg.use_pallas_agg)
        h = h + mlp_apply(lp["node_mlp"], jnp.concatenate([h, agg], axis=-1))
    return mlp_apply(p["dec"], h)


# ------------------------------------------------------------ public API
_APPLY = {"graphsage": _sage_apply, "gat": _gat_apply,
          "gatedgcn": _ggcn_apply, "meshgraphnet": _mgn_apply}


def gnn_init(cfg: GNNConfig, key, d_in: int, d_edge: int = 0,
             n_classes: int = 0) -> Params:
    if cfg.kind == "graphsage":
        p = _sage_init(cfg, key, d_in)
    elif cfg.kind == "gat":
        # the last GAT layer gives the classes itself, as published
        return _gat_init(cfg, key, d_in, n_classes)
    elif cfg.kind == "gatedgcn":
        p = _ggcn_init(cfg, key, d_in, d_edge)
    elif cfg.kind == "meshgraphnet":
        p = _mgn_init(cfg, key, d_in, d_edge)
    else:
        raise ValueError(cfg.kind)
    if n_classes:
        kh = jax.random.fold_in(key, 999)
        d_feat_out = {
            "graphsage": cfg.d_hidden,
            "gatedgcn": cfg.d_hidden,
            "meshgraphnet": max(cfg.d_out, 1),
        }[cfg.kind]
        p["head"] = dense_init(kh, d_feat_out, n_classes, cfg.dtype)
    return p


def gnn_apply(cfg: GNNConfig, params: Params, batch: GraphBatch
              ) -> jnp.ndarray:
    """Node representations (or regression output for meshgraphnet)."""
    out = _APPLY[cfg.kind](cfg, params, batch)
    if "head" in params:
        out = out @ params["head"]
    return out


def subgraph_batch(sub, features: jnp.ndarray) -> GraphBatch:
    """Forward-ready :class:`GraphBatch` from a sampled ``Subgraph``.

    The serve-path bridge between the preprocessing pipeline and the
    model zoo: features are gathered through the subgraph's old-VID order,
    ``edge_dst`` is rebuilt from the CSC pointers (``searchsorted`` over
    the edge positions — the same reconstruction ``data/sampler.py``
    uses), and ``ptr`` is attached so every segment reduction lowers
    scatter-free. Labels are placeholders: serving consumes logits, not
    losses.
    """
    from repro.core.pipeline import gather_features  # models ← core only
    feats = gather_features(sub, features)
    n_cap = sub.order.shape[0]
    e_cap = sub.csc.idx.shape[0]
    ptr = sub.csc.ptr[:n_cap + 1]
    pos = jnp.arange(e_cap, dtype=jnp.int32)
    dst = (jnp.searchsorted(ptr, pos, side="right").astype(jnp.int32) - 1)
    dst = jnp.where(pos < sub.csc.n_edges, dst, SEN)
    return GraphBatch(edge_dst=dst, edge_src=sub.csc.idx, node_feat=feats,
                      labels=jnp.zeros((n_cap,), jnp.int32),
                      label_mask=jnp.zeros((n_cap,), bool), ptr=ptr)


def gnn_apply_batched(cfg: GNNConfig, params: Params, batch: GraphBatch
                      ) -> jnp.ndarray:
    """Forward over a stack of padded subgraph batches (every ``batch``
    leaf carries a leading [S] slot axis; ``vmap`` runs one lane per
    slot). Each lane computes exactly what ``gnn_apply`` computes on that
    lane's own batch — the bit-equality ``tests/test_gnn_serve.py``
    asserts end to end."""
    return jax.vmap(lambda b: gnn_apply(cfg, params, b))(batch)


def pool_graphs(batch: GraphBatch, h: jnp.ndarray) -> jnp.ndarray:
    """Mean-pool node outputs per graph (batched-small-graphs shapes)."""
    gid = batch.graph_ids
    s = jax.ops.segment_sum(h, gid, num_segments=batch.n_graphs)
    c = jax.ops.segment_sum(jnp.ones((h.shape[0], 1), h.dtype), gid,
                            num_segments=batch.n_graphs)
    return s / jnp.maximum(c, 1.0)


def gnn_loss(cfg: GNNConfig, params: Params, batch: GraphBatch
             ) -> jnp.ndarray:
    from .common import cross_entropy
    out = gnn_apply(cfg, params, batch)
    graph_level = batch.graph_ids is not None
    if graph_level:
        out = pool_graphs(batch, out)
    if cfg.d_out and cfg.kind == "meshgraphnet":
        err = (out.astype(jnp.float32) - batch.labels.astype(jnp.float32))
        m = batch.label_mask[:, None].astype(jnp.float32)
        return jnp.sum(err * err * m) / jnp.maximum(jnp.sum(m), 1.0)
    return cross_entropy(out, batch.labels,
                         batch.label_mask.astype(jnp.float32))
