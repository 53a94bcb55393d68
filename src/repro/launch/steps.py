"""Cell builder: (architecture × input shape × mesh) → concrete step plan.

A ``Cell`` bundles the step function, ShapeDtypeStruct stand-ins for every
input (no device allocation), the NamedSharding trees for jit, and donation
info. launch/dryrun.py lowers+compiles cells; launch/train.py feeds them
real data on small meshes.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import GNN_SHAPES, LM_SHAPES, RECSYS_SHAPES, get_arch, \
    get_config
from repro.dist.collectives import seq_sharded_decode_attn_fn
from repro.dist.sharding import (dlrm_param_shardings, dp_axes,
                                 gnn_batch_shardings,
                                 lm_cache_shardings, lm_param_shardings,
                                 model_axis_size, replicated)
from repro.models.dlrm import (DLRMConfig, dlrm_forward, dlrm_init,
                               dlrm_loss, dlrm_retrieval)
from repro.models.gnn import GNNConfig, GraphBatch, gnn_init, gnn_loss
from repro.models.transformer import (LMConfig, lm_decode_step, lm_init,
                                      lm_loss, lm_prefill, make_cache)
from repro.train.optim import AdamWConfig, adamw_init, adamw_update

SDS = jax.ShapeDtypeStruct


@dataclasses.dataclass
class Cell:
    arch_id: str
    shape_name: str
    fn: Callable
    args: tuple  # pytrees of ShapeDtypeStruct
    in_shardings: tuple
    donate_argnums: tuple = ()
    note: str = ""
    skipped: str = ""  # non-empty → documented skip

    @property
    def key(self) -> str:
        return f"{self.arch_id}__{self.shape_name}"


def _opt_shardings(mesh: Mesh, p_shard):
    return {"m": p_shard, "v": p_shard,
            "step": NamedSharding(mesh, P())}


def _eval_shape(fn):
    return jax.eval_shape(fn)


# ================================================================== LM cells
def _lm_cell(arch_id: str, shape_name: str, mesh: Mesh) -> Cell:
    spec = get_arch(arch_id)
    base: LMConfig = get_config(arch_id)
    ma = model_axis_size(mesh)
    cfg = base.padded(ma)
    dims = LM_SHAPES[shape_name]
    b, s = dims["global_batch"], dims["seq_len"]
    dp = dp_axes(mesh)

    if shape_name in spec.skips:
        return Cell(arch_id, shape_name, lambda: None, (), (),
                    skipped=spec.skips[shape_name])

    from repro.dist.hints import layout as layout_ctx

    params_shape = _eval_shape(lambda: lm_init(cfg, jax.random.PRNGKey(0)))
    kind = dims["kind"]
    lm_layout = cfg.train_layout if kind == "train" else "tp"
    if lm_layout == "dp_only":
        p_shard = replicated(mesh, params_shape)
    else:
        p_shard = lm_param_shardings(mesh, params_shape, fsdp=True,
                                     n_experts=cfg.moe_experts)

    if kind == "train":
        big = cfg.n_layers * cfg.d_model > 200_000
        opt_cfg = AdamWConfig(mom_dtype=jnp.bfloat16
                              if big or lm_layout == "dp_only"
                              else jnp.float32)
        opt_shape = _eval_shape(
            lambda: adamw_init(params_shape, opt_cfg.mom_dtype))
        o_shard = _opt_shardings(mesh, p_shard)
        tokens = SDS((b, s), jnp.int32)
        if lm_layout == "dp_only":
            # batch over (data, model); on multi-pod the sequence splits
            # over 'pod' (context DP) so every chip holds distinct tokens
            bdp = tuple(a for a in ("data", "model") if a in mesh.axis_names)
            sdp = "pod" if "pod" in mesh.axis_names else None
            t_shard = NamedSharding(mesh, P(bdp, sdp))
        else:
            t_shard = NamedSharding(mesh, P(dp, None))
        # grads must stay FSDP-sharded like params: without this constraint
        # GSPMD accumulates the scan-carried grad buffers gathered over the
        # data axis (observed +39 GB/device on grok-1).
        p_spec = jax.tree.map(lambda s: s.spec, p_shard)

        def train_step(params, opt_state, tokens):
            with layout_ctx(lm_layout):
                loss, grads = jax.value_and_grad(
                    lambda p: lm_loss(cfg, p, tokens))(params)
                grads = jax.lax.with_sharding_constraint(grads, p_spec)
                new_p, new_o, metrics = adamw_update(opt_cfg, grads,
                                                     opt_state, params)
            return new_p, new_o, {"loss": loss, **metrics}

        return Cell(arch_id, shape_name, train_step,
                    (params_shape, opt_shape, tokens),
                    (p_shard, o_shard, t_shard), donate_argnums=(0, 1),
                    note="train_step")

    if kind == "prefill":
        tokens = SDS((b, s), jnp.int32)
        t_shard = NamedSharding(mesh, P(dp, None))

        def prefill_step(params, tokens):
            return lm_prefill(cfg, params, tokens)

        return Cell(arch_id, shape_name, prefill_step,
                    (params_shape, tokens), (p_shard, t_shard),
                    note="serve_step (prefill)")

    # decode: one new token against a seq_len KV cache
    seq_sharded = b == 1  # long-context: shard the sequence, not the batch
    cache_shape = _eval_shape(lambda: make_cache(cfg, b, s))
    c_shard = lm_cache_shardings(mesh, cache_shape, seq_sharded=seq_sharded)
    tokens = SDS((b, 1), jnp.int32)
    t_shard = NamedSharding(mesh, P(dp if not seq_sharded else None, None))
    pos = SDS((), jnp.int32)
    pos_shard = NamedSharding(mesh, P())

    # long_500k: route cache attention through the sequence-sharded
    # LSE-combine collective so decode reads only the local cache shard.
    attn = seq_sharded_decode_attn_fn(mesh) if seq_sharded else None

    def decode_step(params, cache, tokens, pos):
        return lm_decode_step(cfg, params, cache, tokens, pos, attn_fn=attn)

    return Cell(arch_id, shape_name, decode_step,
                (params_shape, cache_shape, tokens, pos),
                (p_shard, c_shard, t_shard, pos_shard),
                donate_argnums=(1,),
                note="serve_step (decode)"
                + (", sequence-sharded KV (LSE-combined decode collective)"
                   if seq_sharded else ""))


# ================================================================= GNN cells
def _pad32(x: int) -> int:
    """Pad node/edge counts to a multiple of 32 (lcm of all dp extents):
    SENTINEL edges and mask=False nodes make padding semantically free."""
    return -(-x // 32) * 32


def _gnn_batch_specs(cfg: GNNConfig, shape_name: str) -> GraphBatch:
    d = GNN_SHAPES[shape_name]
    has_edge_feat = cfg.kind in ("gatedgcn", "meshgraphnet")
    node_reg = cfg.kind == "meshgraphnet" and cfg.d_out > 0

    if d["kind"] == "full_graph":
        n, e = _pad32(d["n_nodes"]), _pad32(d["n_edges"])
        g = None
        n_graphs = 1
        lbl = (SDS((n, cfg.d_out), jnp.float32) if node_reg
               else SDS((n,), jnp.int32))
        mask = SDS((n,), jnp.bool_)
    elif d["kind"] == "minibatch":
        bnodes, (f1, f2) = d["batch_nodes"], d["fanout"]
        n = _pad32(bnodes + bnodes * f1 + bnodes * f1 * f2)
        e = _pad32(bnodes * f1 + bnodes * f1 * f2)
        g = None
        n_graphs = 1
        lbl = (SDS((n, cfg.d_out), jnp.float32) if node_reg
               else SDS((n,), jnp.int32))
        mask = SDS((n,), jnp.bool_)
    else:  # batched_graphs (molecule)
        bsz = d["batch"]
        n = _pad32(d["n_nodes"] * bsz)
        e = _pad32(d["n_edges"] * bsz)
        g = SDS((n,), jnp.int32)
        n_graphs = bsz
        lbl = (SDS((bsz, cfg.d_out), jnp.float32) if node_reg
               else SDS((bsz,), jnp.int32))
        mask = SDS((bsz,), jnp.bool_)

    return GraphBatch(
        edge_dst=SDS((e,), jnp.int32),
        edge_src=SDS((e,), jnp.int32),
        node_feat=SDS((n, d["d_feat"]), jnp.float32),
        labels=lbl,
        label_mask=mask,
        edge_feat=SDS((e, 4), jnp.float32) if has_edge_feat else None,
        graph_ids=g,
        n_graphs=n_graphs,
    )


def _gnn_cell(arch_id: str, shape_name: str, mesh: Mesh) -> Cell:
    cfg: GNNConfig = get_config(arch_id)
    d = GNN_SHAPES[shape_name]
    node_reg = cfg.kind == "meshgraphnet" and cfg.d_out > 0
    n_classes = 0 if node_reg else d["n_classes"]
    batch_spec = _gnn_batch_specs(cfg, shape_name)

    params_shape = _eval_shape(lambda: gnn_init(
        cfg, jax.random.PRNGKey(0), d_in=d["d_feat"], d_edge=4,
        n_classes=n_classes))
    p_shard = replicated(mesh, params_shape)
    opt_cfg = AdamWConfig()
    opt_shape = _eval_shape(lambda: adamw_init(params_shape))
    o_shard = _opt_shardings(mesh, p_shard)
    b_shard = gnn_batch_shardings(mesh, batch_spec)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: gnn_loss(cfg, p, batch))(params)
        new_p, new_o, metrics = adamw_update(opt_cfg, grads, opt_state,
                                             params)
        return new_p, new_o, {"loss": loss, **metrics}

    return Cell(arch_id, shape_name, train_step,
                (params_shape, opt_shape, batch_spec),
                (p_shard, o_shard, b_shard), donate_argnums=(0, 1),
                note=f"train_step ({d['kind']})")


# ============================================================== recsys cells
def _recsys_cell(arch_id: str, shape_name: str, mesh: Mesh) -> Cell:
    cfg: DLRMConfig = get_config(arch_id)
    d = RECSYS_SHAPES[shape_name]
    dp = dp_axes(mesh)

    params_shape = _eval_shape(lambda: dlrm_init(cfg, jax.random.PRNGKey(0)))
    p_shard = dlrm_param_shardings(mesh, params_shape)

    if d["kind"] == "train":
        b = d["batch"]
        opt_cfg = AdamWConfig()
        opt_shape = _eval_shape(lambda: adamw_init(params_shape))
        o_shard = _opt_shardings(mesh, p_shard)
        dense = SDS((b, cfg.n_dense), jnp.float32)
        idx = SDS((b, cfg.n_sparse, cfg.hot), jnp.int32)
        lbl = SDS((b,), jnp.float32)
        shards = (NamedSharding(mesh, P(dp, None)),
                  NamedSharding(mesh, P(dp, None, None)),
                  NamedSharding(mesh, P(dp)))

        def train_step(params, opt_state, dense, idx, lbl):
            loss, grads = jax.value_and_grad(
                lambda p: dlrm_loss(cfg, p, dense, idx, lbl))(params)
            new_p, new_o, metrics = adamw_update(opt_cfg, grads, opt_state,
                                                 params)
            return new_p, new_o, {"loss": loss, **metrics}

        return Cell(arch_id, shape_name, train_step,
                    (params_shape, opt_shape, dense, idx, lbl),
                    (p_shard, o_shard) + shards, donate_argnums=(0, 1),
                    note="train_step")

    if d["kind"] == "serve":
        b = d["batch"]
        dense = SDS((b, cfg.n_dense), jnp.float32)
        idx = SDS((b, cfg.n_sparse, cfg.hot), jnp.int32)
        shards = (NamedSharding(mesh, P(dp, None)),
                  NamedSharding(mesh, P(dp, None, None)))

        def serve_step(params, dense, idx):
            return dlrm_forward(cfg, params, dense, idx)

        return Cell(arch_id, shape_name, serve_step,
                    (params_shape, dense, idx), (p_shard,) + shards,
                    note="serve_step")

    # retrieval: 1 query vs n_candidates — batched scoring + top-k
    nc = d["n_candidates"]
    f_cand = 2
    f_user = cfg.n_sparse - f_cand
    dense = SDS((1, cfg.n_dense), jnp.float32)
    uidx = SDS((1, f_user, cfg.hot), jnp.int32)
    cidx = SDS((nc, f_cand, cfg.hot), jnp.int32)
    shards = (NamedSharding(mesh, P(None, None)),
              NamedSharding(mesh, P(None, None, None)),
              NamedSharding(mesh, P(dp, None, None)))

    def retrieval_step(params, dense, uidx, cidx):
        return dlrm_retrieval(cfg, params, dense, uidx, cidx)

    return Cell(arch_id, shape_name, retrieval_step,
                (params_shape, dense, uidx, cidx), (p_shard,) + shards,
                note="serve_step (retrieval, batched-dot)")


# ===================================================== paper-technique cells
def preprocess_cells(mesh: Mesh) -> list[Cell]:
    """The AutoGNN engine itself as dry-run cells (beyond the 40):

    * autognn-convert / reddit: distributed COO→CSC conversion through
      ``engine.shard.shard_convert`` — per-device native sorts under
      shard_map, cross-device merge rounds, tiled pointer set-count
    * autognn-sample / reddit-minibatch: Selecting+Reindexing with the graph
      replicated and batch nodes sharded — DGL-style data-parallel sampling
    * autognn-preprocess / reddit-e2e: the full sharded workflow
      (``engine.shard.shard_preprocess``) — convert + sample as one program
    """
    from repro.core import COO, CSC, EngineConfig, sample_subgraph
    from repro.core.graph import next_pow2
    from repro.engine.shard import shard_convert, shard_preprocess

    dp = dp_axes(mesh)
    n, e = 232965, 114615892
    cap = next_pow2(e)  # 2^27
    cells = []

    coo_spec = COO(dst=SDS((cap,), jnp.int32), src=SDS((cap,), jnp.int32),
                   n_edges=SDS((), jnp.int32), n_nodes=n)
    coo_shard = COO(dst=NamedSharding(mesh, P(dp)),
                    src=NamedSharding(mesh, P(dp)),
                    n_edges=NamedSharding(mesh, P()), n_nodes=n)
    ecfg = EngineConfig()

    def convert_step(coo):
        return shard_convert(mesh, coo, ecfg)

    cells.append(Cell("autognn-convert", "reddit", convert_step,
                      (coo_spec,), (coo_shard,),
                      note="COO→CSC conversion, edges sharded over dp "
                           "(engine.shard)"))

    csc_spec = CSC(ptr=SDS((n + 1,), jnp.int32), idx=SDS((cap,), jnp.int32),
                   n_edges=SDS((), jnp.int32), n_nodes=n)
    csc_shard = CSC(ptr=NamedSharding(mesh, P()),
                    idx=NamedSharding(mesh, P()),
                    n_edges=NamedSharding(mesh, P()), n_nodes=n)
    bn = SDS((1024,), jnp.int32)
    bn_shard = NamedSharding(mesh, P(dp))
    key_spec = SDS((2,), jnp.uint32)
    key_shard = NamedSharding(mesh, P())

    def sample_step(csc, batch_nodes, key):
        return sample_subgraph(csc, batch_nodes, (15, 10), key, ecfg)

    cells.append(Cell("autognn-sample", "reddit-minibatch", sample_step,
                      (csc_spec, bn, key_spec),
                      (csc_shard, bn_shard, key_shard),
                      note="Selecting+Reindexing, batch sharded over dp"))

    def e2e_step(coo, batch_nodes, key):
        return shard_preprocess(mesh, coo, batch_nodes, (15, 10), key, ecfg)

    cells.append(Cell("autognn-preprocess", "reddit-e2e", e2e_step,
                      (coo_spec, bn, key_spec),
                      (coo_shard, bn_shard, key_shard),
                      note="full sharded preprocess workflow (engine.shard)"))
    return cells


# ------------------------------------------------------------------- public
def build_cell(arch_id: str, shape_name: str, mesh: Mesh) -> Cell:
    family = get_arch(arch_id).family
    if family == "lm":
        return _lm_cell(arch_id, shape_name, mesh)
    if family == "gnn":
        return _gnn_cell(arch_id, shape_name, mesh)
    if family == "recsys":
        return _recsys_cell(arch_id, shape_name, mesh)
    raise ValueError(family)
