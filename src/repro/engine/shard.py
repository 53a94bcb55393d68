"""Mesh-sharded preprocessing: data-parallel Ordering + tiled Reshaping.

The paper's UPE region processes edge chunks in parallel lanes; on a TPU
mesh the lanes *are* the devices. This module shards the preprocessing
pipeline over the data-parallel mesh axes with explicit ``shard_map``:

* **Ordering** — the padded COO edge buffer is split into one contiguous
  span per dp device. Each device runs the chunked LSD radix sort plus its
  local merge rounds (one sorted run per device), then ``log2(n_dev)``
  cross-device merge rounds complete the global sort. A stable sort has a
  canonical output — every merge-tree refinement yields the same (key, val)
  arrays — so the result is *bit-identical* to the single-device
  ``core.ordering.edge_ordering`` regardless of how chunks map to devices.
* **Reshaping** — the pointer array is a tiled set-count: the target VID
  range is sharded over devices and each shard ranks its targets against
  the (replicated) sorted dst stream. ``rank_in_sorted`` is an independent
  log-depth binary search per target, so sharded == single-device exactly.
* **Selecting/Reindexing** operate on the sampled subgraph (batch-sized,
  not graph-sized) and reuse ``core.pipeline.sample_subgraph`` unchanged.

``shard_preprocess`` therefore returns bit-identical ``Subgraph``s to
``pipeline.preprocess`` for the same inputs — tested on an 8-virtual-device
mesh in tests/test_engine_shard.py.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro.core.costmodel import (EngineConfig, Workload,
                                  pointer_reindex_strategy,
                                  resolve_sort_strategy)
from repro.core.graph import COO, CSC, SENTINEL, Subgraph
from repro.core.ordering import (_bits_for, _chunk_sort,
                                 _global_radix_passes, edge_ordering,
                                 merge_rounds, stable_sort_by_key)
from repro.core.pipeline import kernel_fns
from repro.core.pipeline import preprocess as _preprocess_single
from repro.core.pipeline import sample_subgraph
from repro.core.set_count import rank_in_sorted
from repro.dist.sharding import _axes_size, dp_axes


def _dp(mesh: Mesh | None) -> tuple[tuple[str, ...], int]:
    if mesh is None:
        return (), 1
    explicit = [a for a, t in zip(mesh.axis_names, mesh.axis_types)
                if t == AxisType.Explicit]
    if explicit:
        raise ValueError(
            f"mesh axes {explicit} are Explicit; the sharded engine runs "
            f"under GSPMD and needs Auto axes — build the mesh with "
            f"repro.launch.mesh.make_mesh")
    dp = dp_axes(mesh)
    return dp, _axes_size(mesh, dp)


def shard_sort_by_key(mesh: Mesh, keys: jnp.ndarray, vals: jnp.ndarray,
                      key_bound: int, chunk: int | None = None,
                      radix_bits: int = 4, map_batch: int = 0,
                      chunk_sort_fn=None, merge_fn=None,
                      strategy: str = "chunked_merge", fan_in: int = 2,
                      digit_pass_fn=None
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Global stable sort with the local sort stage sharded over devices.

    Each dp shard owns ``n / n_dev`` contiguous elements and sorts them to
    one run — chunk-radix-sort + local k-ary merge ladder under
    ``strategy="chunked_merge"`` (all lanes vmapped — on the sharded path
    the devices ARE the lanes), or the merge-free tiled global-radix digit
    passes under ``strategy="global_radix"`` (each device's span IS the
    "whole array" of ``core.ordering._global_radix_passes``). Either way
    the remaining ``log2(n_dev)`` cross-device merge rounds run unchanged
    on the global arrays (GSPMD collectives) — the strategy reconfigures
    the per-device reduction structure, not the collective schedule.
    ``chunk_sort_fn`` swaps in the Pallas UPE kernel, ``merge_fn`` the
    fused VMEM merge kernel for the *device-local* merge rounds, and
    ``digit_pass_fn`` the Pallas tiled digit-pass pair, same contracts as
    ``core.ordering.stable_sort_by_key``.
    Falls back to the single-device sorter — honoring ``map_batch`` (the
    UPE lane bound) there — when the mesh has no dp extent or the buffer
    does not divide. ``vals=None`` runs the whole sharded stack keys-only
    (the packed Ordering path: no payload crosses a device boundary).

    Example (1-device mesh exercises the fallback; an n-device mesh is
    bit-identical by the stable-sort argument above)::

        >>> import jax, jax.numpy as jnp
        >>> from repro.launch.mesh import make_mesh
        >>> mesh = make_mesh((1,), ("data",))
        >>> ks, vs = shard_sort_by_key(mesh, jnp.array([3, 1, 2, 0]),
        ...                            jnp.arange(4), key_bound=4, chunk=4)
        >>> ks.tolist(), vs.tolist()
        ([0, 1, 2, 3], [3, 1, 2, 0])
        >>> ks, none = shard_sort_by_key(mesh, jnp.array([3, 1, 2, 0]),
        ...                              None, key_bound=4, chunk=4)
        >>> none is None  # keys-only: no payload moved
        True
    """
    from repro.core.ordering import DEFAULT_CHUNK
    n = keys.shape[0]
    chunk = DEFAULT_CHUNK if chunk is None else chunk
    dp, nd = _dp(mesh)
    # the merge tree needs pow2 run counts: device count AND local span
    if nd <= 1 or nd & (nd - 1) or n % nd or (n // nd) & (n // nd - 1):
        return stable_sort_by_key(keys, vals, key_bound, chunk=min(chunk, n),
                                  radix_bits=radix_bits,
                                  map_batch=map_batch,
                                  chunk_sort_fn=chunk_sort_fn,
                                  merge_fn=merge_fn, strategy=strategy,
                                  fan_in=fan_in,
                                  digit_pass_fn=digit_pass_fn)
    local = n // nd
    chunk = min(chunk, local)
    key_bits = _bits_for(key_bound)
    clipped = jnp.minimum(keys, jnp.int32(key_bound))

    def local_sorted_run(k_l, v_l):
        """One device's span → one sorted run, per the strategy."""
        if strategy == "xla_sort":  # device-local native sort
            if v_l is None:
                return jnp.sort(k_l), None
            return jax.lax.sort([k_l, v_l], num_keys=1, is_stable=True)
        if strategy == "global_radix":
            return _global_radix_passes(k_l, v_l, key_bits, chunk,
                                        radix_bits,
                                        digit_pass_fn=digit_pass_fn)
        if chunk_sort_fn is None:
            ks, vs = _chunk_sort(k_l, v_l, chunk, key_bits, radix_bits,
                                 map_batch=0)
        else:
            ks, vs = chunk_sort_fn(k_l, v_l, chunk, key_bits)
        return merge_rounds(ks, vs, chunk, merge_fn=merge_fn,
                            fan_in=fan_in)

    if vals is None:
        fn = shard_map(lambda k_l: local_sorted_run(k_l, None)[0],
                       mesh=mesh, in_specs=(P(dp),),
                       out_specs=P(dp), check_vma=False)
        ks, _ = merge_rounds(fn(clipped), None, local)
        return jnp.where(ks >= key_bound, SENTINEL, ks), None

    fn = shard_map(local_sorted_run, mesh=mesh, in_specs=(P(dp), P(dp)),
                   out_specs=(P(dp), P(dp)), check_vma=False)
    ks, vs = fn(clipped, vals)
    ks, vs = merge_rounds(ks, vs, local)
    ks = jnp.where(ks >= key_bound, SENTINEL, ks)
    return ks, vs


# THE Pallas routing rule, shared with core.pipeline.convert/sample_subgraph
# so the sharded engine honors use_pallas (and its radix_bits) instead of
# silently dropping them.
_kernel_fns = kernel_fns


def shard_edge_ordering(mesh: Mesh, coo: COO,
                        cfg: EngineConfig | None = None) -> COO:
    """Sharded edge Ordering: ``core.ordering.edge_ordering``'s key scheme
    (packed single-pass or two-pass LSD, per ``cfg.sort_mode``) with the
    global sorter swapped for the shard_map one.

    Example::

        >>> import jax
        >>> from repro.core.graph import COO
        >>> from repro.launch.mesh import make_mesh
        >>> mesh = make_mesh((1,), ("data",))
        >>> coo = COO.from_arrays([1, 0, 1, 0], [1, 1, 0, 0], n_nodes=2)
        >>> s = shard_edge_ordering(mesh, coo)
        >>> s.dst.tolist(), s.src.tolist()  # sorted by (dst, src)
        ([0, 0, 1, 1], [0, 1, 0, 1])
    """
    cfg = cfg or EngineConfig()
    chunk_sort_fn, _, merge_fn, digit_pass_fn, _, _ = _kernel_fns(cfg)
    strategy = resolve_sort_strategy(
        cfg, Workload(n=coo.n_nodes, e=coo.capacity))

    def sort_fn(k, v, bound):
        return shard_sort_by_key(mesh, k, v, bound, chunk=cfg.w_upe,
                                 radix_bits=cfg.radix_bits,
                                 map_batch=cfg.n_upe,
                                 chunk_sort_fn=chunk_sort_fn,
                                 merge_fn=merge_fn, strategy=strategy,
                                 fan_in=cfg.merge_fan_in,
                                 digit_pass_fn=digit_pass_fn)

    return edge_ordering(coo, sort_fn=sort_fn, mode=cfg.sort_mode)


def shard_pointer_array(mesh: Mesh, sorted_dst: jnp.ndarray,
                        n_nodes: int, count_fn=None, unroll: bool = False,
                        rank_fn=None) -> jnp.ndarray:
    """Sharded Reshaping: ptr[v] = rank of v in the sorted dst stream, the
    target range tiled over devices (each shard one SCR tile row-block).
    ``count_fn`` swaps in the Pallas SCR kernel; ``rank_fn`` the fused
    rank-epilogue kernel and ``unroll=True`` the statically-unrolled jnp
    search (same fused/unfused contract as
    ``core.reshaping.build_pointer_array`` — the per-shard tile runs it
    over its target block).

    Example::

        >>> import jax, jax.numpy as jnp
        >>> from repro.launch.mesh import make_mesh
        >>> mesh = make_mesh((1,), ("data",))
        >>> shard_pointer_array(mesh, jnp.array([0, 0, 1, 1]),
        ...                     n_nodes=2).tolist()
        [0, 2, 4]
    """
    dp, nd = _dp(mesh)
    targets = jnp.arange(n_nodes + 1, dtype=jnp.int32)

    def tile(dst_full, t_l):
        if rank_fn is not None:
            return rank_fn(dst_full, t_l, "left")
        if count_fn is not None:
            return count_fn(dst_full, t_l)
        return rank_in_sorted(dst_full, t_l, side="left", unroll=unroll)

    if nd <= 1:
        return tile(sorted_dst, targets)
    pad = (-(n_nodes + 1)) % nd
    t_pad = jnp.pad(targets, (0, pad), constant_values=n_nodes)
    fn = shard_map(tile, mesh=mesh, in_specs=(P(), P(dp)), out_specs=P(dp),
                   check_vma=False)
    return fn(sorted_dst, t_pad)[:n_nodes + 1]


def shard_convert(mesh: Mesh, coo: COO,
                  cfg: EngineConfig | None = None) -> CSC:
    """Sharded graph conversion: Ordering + Reshaping over the dp axes.

    Example::

        >>> import jax
        >>> from repro.core.graph import COO
        >>> from repro.launch.mesh import make_mesh
        >>> mesh = make_mesh((1,), ("data",))
        >>> coo = COO.from_arrays([1, 0, 1, 0], [1, 1, 0, 0], n_nodes=2)
        >>> csc = shard_convert(mesh, coo)
        >>> csc.ptr.tolist(), csc.idx.tolist()
        ([0, 2, 4], [0, 1, 0, 1])
    """
    cfg = cfg or EngineConfig()
    _, count_fn, _, _, rank_fn, _ = _kernel_fns(cfg)
    sorted_coo = shard_edge_ordering(mesh, coo, cfg)
    ptr_fused = pointer_reindex_strategy(
        cfg, Workload(n=coo.n_nodes, e=coo.capacity)) == "fused"
    ptr = shard_pointer_array(mesh, sorted_coo.dst, coo.n_nodes,
                              count_fn=count_fn, unroll=ptr_fused,
                              rank_fn=rank_fn if ptr_fused else None)
    return CSC(ptr=ptr, idx=sorted_coo.src, n_edges=coo.n_edges,
               n_nodes=coo.n_nodes)


def shard_preprocess(mesh: Mesh, coo: COO, batch_nodes: jnp.ndarray,
                     fanouts: tuple[int, ...], key: jax.Array,
                     cfg: EngineConfig = EngineConfig()) -> Subgraph:
    """The full AutoGNN workflow with conversion sharded over the mesh.

    Bit-identical to ``pipeline.preprocess(coo, batch_nodes, fanouts, key,
    cfg)``: the sharded sort/rank stages produce the exact same CSC, and
    Selecting/Reindexing run the identical program on it. Falls back to the
    single-device pipeline when the mesh cannot shard this buffer.

    Example::

        >>> import jax, jax.numpy as jnp
        >>> from repro.core.graph import COO
        >>> from repro.launch.mesh import make_mesh
        >>> mesh = make_mesh((1,), ("data",))
        >>> coo = COO.from_arrays([1, 0, 1, 0], [1, 1, 0, 0], n_nodes=2)
        >>> sub = shard_preprocess(mesh, coo, jnp.array([0], jnp.int32),
        ...                        fanouts=(1,), key=jax.random.PRNGKey(0))
        >>> int(sub.order[0])  # the seed keeps VID 0
        0
    """
    _, nd = _dp(mesh)
    if nd <= 1 or coo.capacity % nd:
        return _preprocess_single(coo, batch_nodes, fanouts, key, cfg)
    csc = shard_convert(mesh, coo, cfg)
    return sample_subgraph(csc, batch_nodes, fanouts, key, cfg)


@lru_cache(maxsize=None)
def jit_shard_preprocess(mesh: Mesh):
    """Per-mesh jitted entry point for ``shard_preprocess``.

    Cached on the mesh so repeated service dispatches hit one jit wrapper
    (the sharded analog of the module-level single-device cache).

    Example::

        >>> import jax
        >>> from repro.launch.mesh import make_mesh
        >>> mesh = make_mesh((1,), ("data",))
        >>> jit_shard_preprocess(mesh) is jit_shard_preprocess(mesh)
        True
    """
    # repro: allow-raw-jit — the lru_cache on the mesh IS the module-level
    # cache: one jit wrapper per mesh for the process lifetime, so repeat
    # dispatches reuse one compile cache exactly like service.convert_jit.
    return jax.jit(partial(shard_preprocess, mesh),
                   static_argnames=("fanouts", "cfg"))
